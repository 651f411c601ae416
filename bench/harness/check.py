"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the one with the longest output (filled up from requests still running
where too few finished), is run through the plain reference: one
forward pass over prompt + served tokens.  At each served position the
number read is the gap by which the served token's reference logit lies
below the reference's best logit there.  Greedy serving puts the
reference's best first up to rounding, so a sound run reads a gap of the
order of its bf16 rounding, and the widest gap over the sample is held
against the cell's limit.

The control (``control_gap``) puts the reference computed through fp8
weights in the program's place: at the same positions it reads the gap
of the token the fp8 reference puts first.

The reference is the ``logits_at`` of the configuration's kind module
(``kind``, from ``cell.kind_module``).
"""
from __future__ import annotations

from typing import List

import numpy as np

from harness.traffic import rng_for


# numbers compared whose limit is a least value; every other one's is a most
AT_LEAST = ("tokens_compared",)


def decide(checks: dict) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] >= c["limit"] if name in AT_LEAST
               else c["value"] <= c["limit"] for name, c in checks.items())


def control_decides(checks: dict, ctl: dict) -> bool:
    """``correct`` with the control in the program's place: the run's own
    decision on the control's gap and count (``ctl``, from
    ``control_gap``)."""
    limit = checks["logit_gap"]["limit"]
    return decide(dict(
        checks, logit_gap={"value": ctl["gap"], "limit": limit},
        tokens_compared={"value": ctl["tokens"], "limit": 1}))


def finished(win) -> list:
    return [r for r in win.records
            if r.req.done and r.req.t_done is not None
            and r.req.t_done <= win.t_close]


def sample(records: list, seed: int, n: int, spare: list = ()) -> list:
    """``n`` of the finished ``records`` drawn from ``seed``, the one with
    the longest output among them.  Where fewer than ``n`` finished, the
    sample is filled from ``spare`` (requests still running, compared on
    the tokens they were served), so a slow window still compares."""
    rng = rng_for(seed, 7)
    out = []
    if records:
        longest = max(records, key=lambda r: (len(r.req.output), r.index))
        rest = [r for r in records if r is not longest]
        pick = rng.permutation(len(rest))[:max(n - 1, 0)]
        out = [longest] + [rest[i] for i in sorted(pick)]
    spare = [r for r in spare if len(r.req.output) > 1]
    pick = rng.permutation(len(spare))[:max(n - len(out), 0)]
    return out + [spare[i] for i in sorted(pick)]


def validity_failures(records: list, vocab: int) -> List[str]:
    """Requests whose output breaks the serving contract outright: a
    token outside the vocabulary, more tokens than the budget, or a
    finished request short of its budget (no EOS is ever set)."""
    bad = []
    for r in records:
        out = np.asarray(r.req.output, np.int64)
        if out.size and (out.min() < 0 or out.max() >= vocab):
            bad.append(f"request {r.index}: token outside [0, {vocab})")
        elif len(out) > r.req.max_new_tokens:
            bad.append(f"request {r.index}: {len(out)} tokens > budget")
        elif r.req.done and len(out) != r.req.max_new_tokens:
            bad.append(f"request {r.index}: finished with {len(out)} of "
                       f"{r.req.max_new_tokens} tokens")
    return bad


def _sequence(r):
    prompt = np.asarray(r.req.prompt, np.int32)
    out = np.asarray(r.req.output, np.int32)
    tokens = np.concatenate([prompt, out])[:-1]
    positions = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    return tokens, positions, out


def served_gap(kind, params, model: dict, records: list, pad_to: int
               ) -> dict:
    """Widest reference-logit gap of the served tokens over ``records``."""
    worst, n_tok, per = 0.0, 0, []
    for r in records:
        tokens, pos, out = _sequence(r)
        ref = np.asarray(kind.logits_at(params, model, tokens, pos,
                                        pad_to=pad_to))
        gap = ref.max(-1) - ref[np.arange(len(out)), out]
        per.append(float(gap.max()))
        worst = max(worst, float(gap.max()))
        n_tok += len(out)
    return {"gap": worst, "tokens": n_tok, "per_request": per}


def control_gap(kind, params, model: dict, records: list, pad_to: int
                ) -> dict:
    """The control's reading on the same prompts and served tokens: the
    reference gap of the token the fp8 reference puts first."""
    worst, n_tok, flips = 0.0, 0, 0
    for r in records:
        tokens, pos, out = _sequence(r)
        ref = np.asarray(kind.logits_at(params, model, tokens, pos,
                                        pad_to=pad_to))
        low = np.asarray(kind.logits_at(params, model, tokens, pos,
                                        quant="fp8", pad_to=pad_to))
        top = low.argmax(-1)
        gap = ref.max(-1) - ref[np.arange(len(out)), top]
        flips += int((top != ref.argmax(-1)).sum())
        worst = max(worst, float(gap.max()))
        n_tok += len(out)
    return {"gap": worst, "tokens": n_tok, "flips": flips}
