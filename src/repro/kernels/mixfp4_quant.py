"""Pallas TPU kernel: fused dual-format MixFP4 block quantizer (Algorithm 1).

One pass over the data computes, per g=16 block:
  - the block absmax (shared by both candidate branches),
  - both candidate E4M3 scales (blockmax/6 for E2M1, blockmax/7 for E1M2),
  - both candidate quantizations + their MSEs (branchless RNE, no gathers),
  - the argmin select, the packed 4-bit payload (2/byte) and the scale byte
    with the type bit in the sign position.

This fuses what the naive QDQ path does in two passes (one per candidate)
into a single HBM read + two small writes — the quantizer is the per-step
hot spot of MixFP4 training (it runs on W, X and dY of every GEMM).

Tiling: grid over row-tiles of (bm, K); the full K extent of a tile lives in
VMEM, and bm (the whole of M, or a multiple of 8) shrinks for wider K.
Rows arrive split into their even and odd elements (``kernels.lanes``):
the payload byte is then ``code(even) | code(odd) << 4``, elementwise, and a
16-block's statistics are lane butterflies over its 8-lane groups.  The
kernel emits the scale byte on every lane of its block and the caller
keeps one lane per block.  All of it is 32-bit VPU/XLU work; the E4M3
rounding and encoding are integer arithmetic (the v5e has no fp8 unit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes

__all__ = ["mixfp4_quant_rows", "quant_block_kernel_math"]

_G = 16  # block size (paper g=16); fixed for the kernel


def _rne_e2m1(a: jax.Array) -> jax.Array:
    """Branchless RNE onto the E2M1 magnitude lattice {0,.5,1,1.5,2,3,4,6}.

    Piecewise-uniform regions: step .5 below 2, step 1 in [2,4), step 2 in
    [4,6]; jnp.round is round-half-even, matching IEEE RNE on each region and
    the generic searchsorted oracle (tie-to-even-mantissa).
    """
    a = jnp.clip(a, 0.0, 6.0)
    lo = jnp.round(a * 2.0) * 0.5
    mid = jnp.round(a)
    hi = jnp.round(a * 0.5) * 2.0
    return jnp.where(a < 2.0, lo, jnp.where(a < 4.0, mid, hi))


def _rne_int(a: jax.Array, qmax: float) -> jax.Array:
    """RNE onto the uniform lattice {0..qmax} (E1M2 effective / INT4)."""
    return jnp.clip(jnp.round(a), 0.0, qmax)


def _f32_bits(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _e4m3_rne(x: jax.Array) -> jax.Array:
    """Round to the E4M3 grid (saturating at 448) with integer and f32
    arithmetic: the v5e has no fp8 convert.  Normals keep 3 mantissa bits
    by round-half-even on the f32 bit pattern; below the smallest normal
    (2^-6) the grid is uniform with step 2^-9."""
    x = jnp.clip(x, 0.0, 448.0)
    bits = _f32_bits(x)
    bits = (bits + 0x7FFFF + ((bits >> 20) & 1)) & ~0xFFFFF
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    sub = jnp.round(x * 512.0) * (1.0 / 512.0)
    return jnp.where(x < 2.0**-6, sub, normal)


def _candidate(xe, xo, absmax, target: float, rnd):
    """One Alg. 1 candidate on split rows: E4M3 block scale, lattice
    values, and the block MSE (valid on leader lanes)."""
    s = _e4m3_rne(absmax * (1.0 / target))
    s = jnp.where((absmax > 0) & (s <= 0), 2.0**-9, s)
    s = jnp.where(absmax > 0, s, 1.0)
    inv = 1.0 / s

    def q(x):
        y = x * inv
        r = rnd(jnp.abs(y))
        return jnp.where(y < 0, -r, r)

    qe, qo = q(xe), q(xo)
    err = lanes.block_seqsum16(jnp.square(qe * s - xe),
                               jnp.square(qo * s - xo)) * (1.0 / _G)
    return qe, qo, s, err


def quant_block_kernel_math(xe: jax.Array, xo: jax.Array):
    """Shared per-tile math of Alg. 1 on split rows (``kernels.lanes``):
    ``xe``/``xo`` hold the even/odd elements of rows already divided by the
    level-2 scale; the aligned 8-lane groups of both form one 16-block.

    Returns ``(qe, qo, scale8, type_bits)``: lattice values per element and
    the block scale and type on every lane of the block — exactly what
    ``core.quantize`` gives, including its left-to-right block MSE.
    """
    absmax = lanes.seg_max(jnp.maximum(jnp.abs(xe), jnp.abs(xo)), _G // 2)
    q2e, q2o, s_e2, err2 = _candidate(xe, xo, absmax, 6.0, _rne_e2m1)
    q1e, q1o, s_e1, err1 = _candidate(
        xe, xo, absmax, 7.0, lambda a: _rne_int(a, 7.0))
    # select (ties -> E2M1, matching argmin-first in the oracle)
    t = lanes.seg_broadcast_leader(
        (err1 < err2).astype(jnp.int32), _G // 2) != 0
    return (jnp.where(t, q1e, q2e), jnp.where(t, q1o, q2o),
            jnp.where(t, s_e1, s_e2), t)


def _encode_nibbles(q: jax.Array, t: jax.Array) -> jax.Array:
    """values-on-lattice + type -> int32 4-bit codes [s|p2p1p0]."""
    sign = jnp.where(q < 0, 8, 0)
    a = jnp.abs(q)
    # E2M1 payload index: 2*a below 2 (codes 0..4 at idx a/0.5), then 4+ (a-2)
    # for {2,3,4}->{4,5,6}, then 7 for 6.  Derived from the lattice layout.
    idx2 = jnp.where(a < 2.0, a * 2.0, jnp.where(a < 6.0, a + 2.0, 7.0))
    # E1M2 effective payload == integer level itself (x2 remap built in)
    return sign | jnp.where(t, a, idx2).astype(jnp.int32)


def _pack_scale(s8: jax.Array, t: jax.Array) -> jax.Array:
    """E4M3-valued f32 scale + type bit -> int32 scale byte {T | e4m3}."""
    bits = _f32_bits(s8)
    normal = (((bits >> 23) - 120) << 3) | ((bits >> 20) & 7)
    sub = jnp.round(s8 * 512.0).astype(jnp.int32)
    mag = jnp.where(s8 < 2.0**-6, sub, normal)
    # Canonicalize: a zero-magnitude scale byte must not carry the type
    # bit.  Byte 0x80 is a *negative-zero* E4M3 scale that the type-in-sign
    # decoder would read as an E1M2 block; a zero scale makes the type
    # moot (every payload decodes to 0), so the canonical encoding of a
    # dead block is 0x00.  The branch guards in quant_block_kernel_math
    # keep s8 > 0 today (all-zero blocks get scale 1.0) — this makes the
    # invariant structural rather than incidental.
    return jnp.where(mag == 0, mag, mag | (t.astype(jnp.int32) << 7))


def _quant_kernel(s32_ref, xe_ref, xo_ref, payload_ref, scale_ref, *,
                  per_row: bool = False):
    if per_row:
        # (bm, 1) row-local scales broadcast over the K extent; the
        # reciprocal-then-multiply sequence matches the scalar branch (and
        # the fused GEMM prologue) op for op, so a given row's bytes are
        # identical whichever entry quantizes it.
        inv = 1.0 / s32_ref[...]
    else:
        inv = 1.0 / s32_ref[0, 0]
    xe = xe_ref[...].astype(jnp.float32) * inv
    xo = xo_ref[...].astype(jnp.float32) * inv
    qe, qo, s8, t = quant_block_kernel_math(xe, xo)
    payload = _encode_nibbles(qe, t) | (_encode_nibbles(qo, t) << 4)
    payload_ref[...] = payload.astype(jnp.uint8)
    # every lane of a block carries its scale byte; the caller keeps lane 8b
    scale_ref[...] = _pack_scale(s8, t).astype(jnp.uint8)


def row_tile(m: int, k: int, budget: int) -> int:
    """Row-tile height for a kernel that keeps whole (bm, k) f32 rows in
    VMEM: the whole of M when it fits ``budget`` bytes, else the largest
    multiple of 8 that does (the (8, 128) tiling rule; the grid then has a
    partial last tile)."""
    cap = max(8, min(256, budget // (4 * max(k, 1))) // 8 * 8)
    return m if m <= cap else cap


def _pick_bm(m: int, k: int) -> int:
    """Quantizer row tile: Mosaic keeps about 16 f32 copies of the tile
    live (split halves, both candidates, their errors), so a 768 KiB tile
    stays near 12 MiB, inside the 16 MiB scoped VMEM limit."""
    return row_tile(m, k, 768 * 1024)


@functools.partial(jax.jit, static_argnames=("interpret", "bm", "per_row"))
def mixfp4_quant_rows(
    x: jax.Array,
    *,
    bm: int | None = None,
    interpret: bool = False,
    scale32: jax.Array | float | None = None,
    per_row: bool = False,
):
    """Quantize (M, K) with 1-D g=16 blocks along K (MixFP4, RNE).

    Returns (payload (M, K//2) uint8, scales (M, K//16) uint8, scale32 f32).
    The per-tensor scale is a global reduction, computed outside the kernel
    (a cheap fused max) and passed in SMEM-style as a (1,1) operand.
    ``scale32`` pins it instead — incremental producers (the packed KV
    cache writes rows at different decode steps) need every row quantized
    under one shared per-tensor scale, not a per-call data-dependent one.

    ``per_row=True`` switches the level-2 scale to a row-local reduction
    (``scaling.row_scale``): the returned scale32 is an (M,) vector and
    each row's bytes depend only on that row — the W4A4 serving contract
    that breaks batch coupling.  ``scale32`` may then pin an (M,) vector.
    """
    m, k = x.shape
    assert k % _G == 0, f"K={k} must be a multiple of {_G}"
    if per_row:
        if scale32 is None:
            amax = jnp.max(jnp.abs(x), axis=-1).astype(jnp.float32)
            # matches scaling.row_scale bit-for-bit (reciprocal multiply)
            s32 = jnp.where(amax > 0, amax * (1.0 / 2688.0), 1.0)
        else:
            s32 = jnp.asarray(scale32, jnp.float32)
        s32 = jnp.broadcast_to(s32.reshape(-1), (m,)).reshape(m, 1)
    elif scale32 is None:
        amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
        # matches scaling.tensor_scale bit-for-bit (reciprocal multiply)
        s32 = jnp.where(amax > 0, amax * (1.0 / 2688.0), 1.0).reshape(1, 1)
    else:
        s32 = jnp.asarray(scale32, jnp.float32).reshape(1, 1)

    if bm is None:
        bm = _pick_bm(m, k)
    grid = (pl.cdiv(m, bm),)
    xe, xo = lanes.split_even_odd(x)

    s32_spec = (pl.BlockSpec((bm, 1), lambda i: (i, 0)) if per_row
                else pl.BlockSpec((1, 1), lambda i: (0, 0)))
    row_spec = pl.BlockSpec((bm, k // 2), lambda i: (i, 0))
    payload, lane_scales = pl.pallas_call(
        functools.partial(_quant_kernel, per_row=per_row),
        grid=grid,
        in_specs=[s32_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, k // 2), jnp.uint8),
            jax.ShapeDtypeStruct((m, k // 2), jnp.uint8),
        ],
        interpret=interpret,
        name="mixfp4_quant_rows",
    )(s32, xe, xo)
    scales = lane_scales[:, ::_G // 2]
    return payload, scales, (s32[:, 0] if per_row else s32[0, 0])
