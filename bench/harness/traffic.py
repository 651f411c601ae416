"""The one traffic generator: turns a mix's data file into requests.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

    loop       "open": requests fall due on a schedule
    rate_rps   mean arrivals per second over the window
    arrivals   "poisson": spread over the whole window; or "onoff" with
               ``on_s``, ``off_s`` and ``burst_factor``: the window opens
               with an on-phase of ``on_s`` seconds, then ``off_s`` silent
               ones, and so on; the arrivals are spread as for "poisson"
               over the on-phases alone, so they come at ``burst_factor``
               = (on_s + off_s) / on_s times the mean rate there
    schedule_seed
               draws the sizes and the arrival times: every run of the mix
               sends this one schedule, whatever its ``--seed``, which
               draws the prompt tokens (and the weights)
    prompt, output
               {"dist": "lognormal", "median", "sigma", "min", "max"} or
               {"dist": "uniform", "min", "max"}: token counts, clipped
    engine     the served engine's sizes (max_batch, max_len, block_size,
               prefill_chunk, pool_tokens)
    check      how many finished requests the correctness check compares
               and the limit of the compared number
    trace_s    length of the traced sub-window of a ``--trace 1`` run

Sizes are the distribution's quantiles at stratified points, and
inter-arrival gaps the exponential's; the schedule seed only permutes
them.  So two schedule seeds differ in which request comes when, never in
how many tokens arrive or how fast.  A run's ``--seed`` changes no work:
with a window of a dozen requests the order is the work, and a bound has
to hold across the driver's fresh seeds.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

ARRIVALS = ("poisson", "onoff")


@dataclass(frozen=True)
class Arrival:
    due_s: float          # offset from the window's start (open loop)
    prompt_len: int
    output_len: int


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] != "open":
        raise ValueError(f"{path}: loop must be open")
    kind = mix.get("arrivals", "poisson")
    if kind not in ARRIVALS:
        raise ValueError(f"{path}: arrivals {kind!r} is none of {ARRIVALS}")
    if kind == "onoff":
        missing = {"on_s", "off_s", "burst_factor"} - set(mix)
        if missing:
            raise ValueError(f"{path}: onoff arrivals need {sorted(missing)}")
        on, off = float(mix["on_s"]), float(mix["off_s"])
        if not (on > 0 and off >= 0):
            raise ValueError(f"{path}: on_s must be > 0 and off_s >= 0")
        if not math.isclose(mix["burst_factor"], (on + off) / on,
                            rel_tol=1e-9):
            raise ValueError(f"{path}: burst_factor {mix['burst_factor']} "
                             f"is not (on_s + off_s) / on_s = "
                             f"{(on + off) / on}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed,
    including ones wider than 32 bits."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified_lengths(spec: dict, n: int, rng: np.random.Generator
                       ) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n, in an order drawn by
    ``rng``."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(_quantile(spec, u))


def _gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """Exponential inter-arrival gaps at stratified quantiles, shuffled,
    scaled to sum to 1."""
    u = (np.arange(n) + 0.5) / n
    g = rng.permutation(-np.log1p(-u))
    return g / g.sum()


def open_schedule(mix: dict, seconds: float) -> List[Arrival]:
    """Every request due inside ``[0, seconds)``: ``round(rate * seconds)``
    of them, the last one before the window closes (for "onoff", before
    the last on-phase inside the window ends)."""
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    rng = rng_for(mix["schedule_seed"], 0)
    prompts = stratified_lengths(mix["prompt"], n, rng)
    outputs = stratified_lengths(mix["output"], n, rng)
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        due = np.cumsum(_gaps(n, rng)) * seconds * n / (n + 1)
    elif kind == "onoff":
        # spread over the on-phases' seconds alone, then each offset moved
        # past the off-phases before it
        on, period = mix["on_s"], mix["on_s"] + mix["off_s"]
        whole, part = divmod(seconds, period)
        t = np.cumsum(_gaps(n, rng)) * (whole * on + min(part, on)) * n / (
            n + 1)
        k = np.floor(t / on)
        due = k * period + (t - k * on)
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    return [Arrival(float(t), int(p), int(o))
            for t, p, o in zip(due, prompts, outputs)]


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    return rng_for(seed, 1 + index).integers(0, vocab, length,
                                             dtype=np.int32)


def longest_context(mix: dict) -> int:
    """Tokens in the longest request the mix can send: prompt + output."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])

