# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Backend resolution shared by the Pallas kernels.

This module is the single backend-resolution path for every kernel
entry point (DESIGN.md §11):

* ``resolve_backend()``  — the jax platform name, resolved once per
  process (``cpu`` / ``tpu`` / ``gpu``);
* ``resolve_interpret`` — the shared auto-detect for ``interpret=None``
  defaults: on a real TPU the kernels compile through Mosaic; everywhere
  else (CPU CI, tests) they run in interpret mode.  An explicit bool wins;
* ``tuned_block_sizes`` — the autotuner winner-cache lookup the template
  instantiations consult at trace time for their default block sizes.
  Winners live in ``results/autotune.<backend>.json`` (committed; see
  ``repro.kernels.autotune`` for the sweep harness).  Controlled by the
  ``REPRO_AUTOTUNE`` env var:

    - unset / ``on``: consult the committed cache; a missing key logs a
      one-line warning (once per key) and falls back to the built-in
      defaults — never a crash;
    - ``off``:   ignore the cache entirely, use the built-in defaults;
    - ``sweep``: re-sweep a missing key on first use and use the fresh
      winner (in-process only; the committed file is not rewritten).
"""
from __future__ import annotations

import json
import logging
import os
from functools import lru_cache

import jax

AUTOTUNE_ENV = "REPRO_AUTOTUNE"

_log = logging.getLogger("repro.kernels")


@lru_cache(maxsize=1)
def resolve_backend() -> str:
    # Resolved once per process: the backend does not change under our feet,
    # and jax.default_backend() is not free on every kernel call.
    return jax.default_backend()


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless running on a real TPU (so TPU runs
    compile instead of silently interpreting); an explicit bool wins."""
    return (resolve_backend() != "tpu") if interpret is None else bool(
        interpret)


# ---------------------------------------------------------------------------
# autotuner winner cache (block sizes per variant/backend/head-dim)
# ---------------------------------------------------------------------------

_RESULTS_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "results"))


def autotune_cache_path(backend: str | None = None) -> str:
    """Path of the winner cache consulted at trace time.  Overridable via
    ``REPRO_AUTOTUNE_CACHE`` (the nightly sweep job points it at a scratch
    file so artifact uploads don't dirty the tree)."""
    override = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if override:
        return override
    return os.path.join(_RESULTS_DIR,
                        f"autotune.{backend or resolve_backend()}.json")


def block_size_key(variant: str, head_dim: int,
                   block_size: int | None = None) -> str:
    """Canonical winner-cache key.  ``block_size`` (the paged allocator's
    block size — it IS the kv tile for paged variants) only participates
    for the paged variants."""
    key = f"{variant}|hd={int(head_dim)}"
    if block_size is not None:
        key += f"|bs={int(block_size)}"
    return key


@lru_cache(maxsize=None)
def _load_winner_cache(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        _log.warning("autotune: could not read winner cache %s (%s); "
                     "built-in defaults apply", path, e)
        return {}
    return data.get("entries", {})


_warned_keys: set[str] = set()
_swept_keys: dict[str, dict] = {}


def tuned_block_sizes(variant: str, head_dim: int, *,
                      block_size: int | None = None,
                      defaults: dict) -> dict:
    """Resolve the block sizes a template instantiation should trace with.

    Returns a dict with exactly the keys of ``defaults`` (e.g.
    ``{"bq": 128, "bk": 128}`` for flash, ``{"pad_to": 8}`` for the paged
    variants).  Cache misses log one warning per key and fall back to
    ``defaults`` — tuning is an optimization, never a correctness gate.
    """
    mode = os.environ.get(AUTOTUNE_ENV, "on").lower()
    if mode == "off":
        return dict(defaults)
    key = block_size_key(variant, head_dim, block_size)
    entry = _load_winner_cache(autotune_cache_path()).get(key)
    if entry is None and mode == "sweep":
        entry = _swept_keys.get(key)
        if entry is None:
            from repro.kernels import autotune
            entry = autotune.sweep_entry(variant, head_dim,
                                         block_size=block_size)
            _swept_keys[key] = entry
    if entry is None:
        if key not in _warned_keys:
            _warned_keys.add(key)
            _log.warning(
                "autotune: no winner for key %r in %s; using defaults %s",
                key, autotune_cache_path(), dict(defaults))
        return dict(defaults)
    out = dict(defaults)
    out.update({k: int(v) for k, v in entry.items() if k in defaults})
    return out
