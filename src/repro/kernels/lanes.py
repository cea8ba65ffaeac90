"""Lane-axis layout shared by the Pallas kernels: the even/odd split.

The wire format packs two K-consecutive 4-bit codes per byte, and the 1-D
block scales cover 16 consecutive values.  Interleaving or grouping lanes
like that inside a TPU kernel needs shape casts that Mosaic refuses
(``(bm, bk) -> (bm, bk/16, 16)``, ``stack(lo, hi).reshape``).  So every
kernel works on rows split into their even and odd elements instead:

* ``e = x[..., 0::2]`` and ``o = x[..., 1::2]`` — the low and high nibble
  of each payload byte, so packing and unpacking are elementwise;
* a 16-value block is the aligned 8-lane group ``[8b, 8b+8)`` of both
  halves, so block reductions are lane butterflies (``pltpu.roll``) and
  never a reshape;
* a dot product over K is ``e·e' + o·o'``.

``split_even_odd``/``merge_even_odd`` run outside the kernels (plain XLA);
the rest runs inside them (and in interpret mode on the CPU, where
``pltpu.roll`` is ``jnp.roll``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["split_even_odd", "merge_even_odd", "shift_down", "shift_up",
           "xor_partner", "seg_max", "seg_broadcast_leader",
           "block_seqsum16", "expand_lanes"]


def split_even_odd(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    return x[..., 0::2], x[..., 1::2]


def merge_even_odd(e: jax.Array, o: jax.Array) -> jax.Array:
    return jnp.stack([e, o], axis=-1).reshape(*e.shape[:-1], 2 * e.shape[-1])


def _lane(shape) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def shift_down(v: jax.Array, k: int) -> jax.Array:
    """out[..., i] = v[..., i + k] (wrapping)."""
    n = v.shape[-1]
    return pltpu.roll(v, (n - k) % n, v.ndim - 1)


def shift_up(v: jax.Array, k: int) -> jax.Array:
    """out[..., i] = v[..., i - k] (wrapping)."""
    return pltpu.roll(v, k % v.shape[-1], v.ndim - 1)


def xor_partner(v: jax.Array, s: int) -> jax.Array:
    """out[..., i] = v[..., i ^ s] for a power of two ``s``."""
    low = (_lane(v.shape) & s) == 0
    return jnp.where(low, shift_down(v, s), shift_up(v, s))


def seg_max(v: jax.Array, seg: int) -> jax.Array:
    """Max over aligned groups of ``seg`` lanes, on every lane of the group
    (exact: max does not depend on the order)."""
    s = 1
    while s < seg:
        v = jnp.maximum(v, xor_partner(v, s))
        s *= 2
    return v


def seg_broadcast_leader(v: jax.Array, seg: int) -> jax.Array:
    """Copy lane ``seg*b`` of every aligned group to the whole group."""
    lane = _lane(v.shape)
    s = 1
    while s < seg:
        v = jnp.where((lane & s) != 0, shift_up(v, s), v)
        s *= 2
    return v


def block_seqsum16(e: jax.Array, o: jax.Array) -> jax.Array:
    """Sum of each 16-value block in natural order, left to right —
    ``((x0 + x1) + x2) + ... + x15``, the order XLA's reduce uses, so the
    quantizer's block MSE matches ``core.quantize`` bit for bit.  Valid on
    each group's leader lane (``8b``) only."""
    acc = e + o
    for k in range(1, 8):
        acc = acc + shift_down(e, k)
        acc = acc + shift_down(o, k)
    return acc


def expand_lanes(v: jax.Array, factor: int) -> jax.Array:
    """(r, c) -> (r, c * factor), each lane repeated ``factor`` times, as an
    MXU product with a 0/1 matrix.  Exact for values that bf16 holds
    exactly (E4M3 scales with the type bit in the sign)."""
    assert factor & (factor - 1) == 0, factor
    c = v.shape[-1]
    w = c * factor
    src = jax.lax.broadcasted_iota(jnp.int32, (c, w), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (c, w), 1)
    sel = ((dst >> (factor.bit_length() - 1)) == src).astype(jnp.bfloat16)
    return jax.lax.dot(v.astype(jnp.bfloat16), sel,
                       preferred_element_type=jnp.float32)
