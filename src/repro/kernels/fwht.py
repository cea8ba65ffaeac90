"""Pallas TPU kernel: grouped random Hadamard transform (RHT hot path).

The WGRAD RHT (Fig. 7) touches both GEMM inputs every backward pass; fusing
sign-flip + the log2(g) butterfly stages into one VMEM pass avoids g
intermediate HBM round-trips.  Groups (default 16, the quantization block)
transform independently, so the kernel tiles rows and keeps the full feature
extent resident.

``fwht_rows_math`` is the sign-flip + butterfly body on natural rows (the
serve-time per-row scale derivation in ``core.qtensor`` calls it);
``fwht_split_math`` is the same butterfly on rows split into even/odd
elements (``kernels.lanes``), which the standalone kernel and the fused
W4A4 GEMM prologue (``mixfp4_gemm_w4a4_fused(rht_signs=)``) run.  Both
apply the same elementwise f32 adds/subs/multiplies to the same operands
(no reductions, no FMA contraction), so the transformed values — and
therefore the dual-format select and the row amax — are bit-identical
between the fused and composed paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes
from repro.kernels.mixfp4_quant import row_tile

__all__ = ["fwht_rows", "fwht_rows_math", "fwht_split_math"]


def fwht_rows_math(x: jax.Array, signs: jax.Array, group: int) -> jax.Array:
    """Sign flip + grouped FWHT butterfly on f32 rows: x (bm, k), signs
    broadcastable to (1, k).  Mirrors ``core.hadamard.rht`` stage for stage
    (same adds/subs, same ``group ** -0.5`` normalization)."""
    bm, k = x.shape
    x = x * signs.reshape(1, k)
    x = x.reshape(bm, k // group, group)
    h = 1
    while h < group:
        x = x.reshape(bm, k // group, group // (2 * h), 2, h)
        a = x[..., 0, :]
        b = x[..., 1, :]
        x = jnp.concatenate(
            [(a + b)[..., None, :], (a - b)[..., None, :]], axis=-2
        ).reshape(bm, k // group, group)
        h *= 2
    x = x * (group ** -0.5)
    return x.reshape(bm, k)


def fwht_split_math(xe: jax.Array, xo: jax.Array, se: jax.Array,
                    so: jax.Array, group: int):
    """``fwht_rows_math`` on split rows (``kernels.lanes``): ``xe``/``xo``
    are the even/odd elements of f32 rows, ``se``/``so`` the matching sign
    halves, shape (1, w).  Natural index ``2i + h`` lives on lane ``i`` of
    half ``h``, so the first stage pairs the halves elementwise and stage
    ``h >= 2`` pairs lane ``i`` with lane ``i ^ (h/2)`` — the same adds and
    subtracts, on the same operands, as the natural-layout butterfly."""
    xe = xe * se
    xo = xo * so
    xe, xo = xe + xo, xe - xo
    lane = jax.lax.broadcasted_iota(jnp.int32, xe.shape, xe.ndim - 1)
    h = 2
    while h < group:
        low = (lane & (h // 2)) == 0
        pe = lanes.xor_partner(xe, h // 2)
        po = lanes.xor_partner(xo, h // 2)
        xe = jnp.where(low, xe + pe, pe - xe)
        xo = jnp.where(low, xo + po, po - xo)
        h *= 2
    norm = group ** -0.5
    return xe * norm, xo * norm


def _fwht_kernel(xe_ref, xo_ref, se_ref, so_ref, oe_ref, oo_ref, *,
                 group: int):
    oe, oo = fwht_split_math(
        xe_ref[...].astype(jnp.float32), xo_ref[...].astype(jnp.float32),
        se_ref[...].astype(jnp.float32), so_ref[...].astype(jnp.float32),
        group)
    oe_ref[...] = oe.astype(oe_ref.dtype)
    oo_ref[...] = oo.astype(oo_ref.dtype)


def _pick_bm(m: int, k: int) -> int:
    """Row tile: a 512 KiB f32 tile keeps the split halves and the
    butterfly temporaries well inside the scoped VMEM limit."""
    return row_tile(m, k, 512 * 1024)


@functools.partial(jax.jit, static_argnames=("group", "bm", "interpret"))
def fwht_rows(
    x: jax.Array,
    signs: jax.Array,
    *,
    group: int = 16,
    bm: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Grouped RHT along the last axis of (M, K); signs shape (K,)."""
    m, k = x.shape
    if group <= 0 or group & (group - 1):
        # mirror core.hadamard.fwht: a non-power-of-two group has no
        # butterfly factorization — the loop below would silently compute
        # a partial transform instead of H_g.
        raise ValueError(
            f"FWHT group must be a power of two, got {group}")
    if k % group:
        raise ValueError(
            f"axis length {k} not divisible by RHT group {group}")
    if signs.shape != (k,):
        raise ValueError(
            f"signs must have shape ({k},), got {signs.shape}")
    if bm is None:
        bm = _pick_bm(m, k)
    xe, xo = lanes.split_even_odd(x)
    se, so = lanes.split_even_odd(signs.reshape(1, k))
    row = pl.BlockSpec((bm, k // 2), lambda i: (i, 0))
    sign = pl.BlockSpec((1, k // 2), lambda i: (0, 0))
    half = jax.ShapeDtypeStruct((m, k // 2), x.dtype)
    oe, oo = pl.pallas_call(
        functools.partial(_fwht_kernel, group=group),
        grid=(pl.cdiv(m, bm),),
        in_specs=[row, row, sign, sign],
        out_specs=[row, row],
        out_shape=[half, half],
        interpret=interpret,
        name="fwht_rows",
    )(xe, xo, se, so)
    return lanes.merge_even_odd(oe, oo)
