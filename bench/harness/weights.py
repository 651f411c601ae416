"""Weights drawn from the seed, on the device, in one jitted call.

Large leaves are drawn slice by slice (``SLICE_ELEMENTS``), so drawing
adds little to the weights' own footprint and the process's peak memory
is the serving's, not the drawing's.

The program under test says only which arrays it expects (their tree,
shapes and dtypes, from ``jax.eval_shape`` of its own init); every value
is drawn here, so the reference and the program read the same weights and
neither takes anything the other made.  Each leaf is drawn in the dtype it
is served in, by a rule on its name:

    matrices                normal / sqrt(fan_in)      (fan_in: axis -2)
    embed                   normal
    norm gains (norm*)      normal * 0.1               (applied as 1 + g)
    anything else           normal * 0.02
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    for k in reversed(path):
        if hasattr(k, "key"):
            return str(k.key)
    return ""


# a leaf larger than this many elements is drawn in slices of its leading
# axis, one after the other, so the random bits of the whole leaf (4 bytes
# an element) never sit on the device at once next to the weights
SLICE_ELEMENTS = 1 << 24


def _slices(n: int, row: int) -> int:
    """How many slices of the leading axis (of ``n``, ``row`` elements
    each) keep one slice under ``SLICE_ELEMENTS``."""
    for k in range(1, n + 1):
        if n % k == 0 and (n // k) * row <= SLICE_ELEMENTS:
            return k
    return n


def _normal(key, shape, dt, scale):
    draw = lambda k, s: jax.random.normal(k, s, dt) * jnp.asarray(scale, dt)
    size = int(np.prod(shape))
    if size <= SLICE_ELEMENTS or len(shape) < 2:
        return draw(key, shape)
    k = _slices(shape[0], size // shape[0])
    part = (shape[0] // k,) + tuple(shape[1:])
    out = jax.lax.map(lambda kk: draw(kk, part), jax.random.split(key, k))
    return out.reshape(shape)


def _draw(key, name: str, sds):
    shape, dt = sds.shape, sds.dtype
    normal = lambda s=1.0: _normal(key, shape, dt, s)
    if name == "embed":
        return normal()
    if name.startswith("norm") or name.endswith("_norm"):
        return normal(0.1)
    if len(shape) >= 2:
        return normal(1.0 / np.sqrt(shape[-2]))
    return normal(0.02)


def make_weights(shapes, seed: int):
    """Arrays for the pytree ``shapes`` (of ``jax.ShapeDtypeStruct``),
    drawn from ``seed`` in one jitted call on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), _leaf_name(path), sds)
            for i, (path, sds) in enumerate(leaves)])

    return build(seed_key(seed))
