"""Pallas TPU kernel: block-scaled MixFP4 GEMM with in-VMEM Fig. 9 decode.

TPU adaptation of the paper's tensor-core datapath (§3.3, DESIGN.md §2):
the packed FP4 payload and type-in-sign scale bytes stream HBM->VMEM; a
branch-free dual-codebook decoder (E2M1 shift path / E1M2 integer path,
selected by the block-shared T bit) expands them to bf16 *with the block
scale fused on the VPU*, and the MXU performs the matmul with f32
accumulation.  Eq. 35's factored-scale dot is restructured to scale-before-
MXU because the 128x128 systolic array cannot emit per-16-element partials.

Three entry points:
  mixfp4_gemm_w4a16      : bf16 activations x packed weight  (serving decode
                           path; weight HBM traffic is 4.5 bits/value)
  mixfp4_gemm_w4a4       : packed activations x packed weight (full FP4 MMA
                           analog; the two-dispatch composition's GEMM half)
  mixfp4_gemm_w4a4_fused : bf16/f32 activations quantized to MixFP4 rows IN
                           THE KERNEL PROLOGUE (Alg. 1 via the shared
                           ``quant_block_kernel_math``), then the same dual-
                           decode MMA — serve-time W4A4 in ONE dispatch per
                           projection instead of quantize_rows + GEMM.

Weight layout (from ``pack_weight_qt``): payload (K//2, N) uint8 with two
K-consecutive nibbles per byte; scales (K//16, N//16) uint8 for the paper's
2-D 16x16 weight tiles.  Activation layout (W4A4): payload (M, K//2), scales
(M, K//16) — 1-D blocks along the contraction axis.  The low nibble of a
byte is the even K index, so the kernels work on the even/odd split of K
(``kernels.lanes``): dense activations arrive as their even and odd K
columns, a decoded weight tile is its even and odd K rows, and each K step
is ``dot(x_even, w_even) + dot(x_odd, w_odd)`` — no lane interleave, which
the TPU compiler cannot lower.

Grid and streaming: the grid is (M/bm, N/bn) with the K loop INSIDE the
kernel.  The activation and weight payload slabs live in HBM and are
streamed into two VMEM slots with manual async copies — the next K slab's
DMA is issued before the current slab is consumed (double buffering) —
while the grid pipeline brings each cell's scale bytes into VMEM; the f32
accumulator block never leaves VMEM scratch.  Accumulation is K-ordered
(``acc += (dot_even + dot_odd) * s32`` per K step), so the fused and
two-dispatch paths are bitwise-comparable.

The fused prologue is bitwise-identical to the composition by construction:
``quant_block_kernel_math`` returns values already ON the 4-bit lattice and
an E4M3-valued block scale, the nibble encode/decode round trip is exact on
both lattices, and the scale byte's pack/unpack is exact — so
``(q * s8).astype(bfloat16)`` equals what ``_expand_act_tile`` reconstructs
from the packed bytes, element for element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes
from repro.kernels.fwht import fwht_split_math
from repro.kernels.mixfp4_quant import quant_block_kernel_math

__all__ = ["mixfp4_gemm_w4a16", "mixfp4_gemm_w4a4", "mixfp4_gemm_w4a4_fused"]

_G = 16


def _decode_scales(scale_bytes: jax.Array):
    """scale byte {T | e4m3[6:0]} -> (f32 scale, int32 T), decoded from the
    integer fields (the v5e has no fp8 convert)."""
    b = scale_bytes.astype(jnp.int32)
    mag = b & 0x7F
    e = mag >> 3
    m = mag & 7
    normal = jax.lax.bitcast_convert_type(((e + 120) << 23) | (m << 20),
                                          jnp.float32)
    sub = m.astype(jnp.float32) * 2.0**-9
    s = jnp.where(e == 0, sub, normal)
    s = jnp.where(mag == 0x7F, jnp.nan, s)         # E4M3fn's only NaN
    return s, b >> 7


def _decode_nibbles(nib: jax.Array, t_full: jax.Array) -> jax.Array:
    """Fig. 9 unified decode, gather-free, from int32 fields.

    E2M1 path: magnitudes {0, .5, 1, 1.5, 2, 3, 4, 6} for payload 0..7 —
    ``p/2`` below 4, ``p-2`` up to 6, then 6 (the 'shift path').
    E1M2 path: effective value == integer payload (the x2 remap folds in).
    """
    nib = nib.astype(jnp.int32)
    p = nib & 0x7
    pf = p.astype(jnp.float32)
    v_e2m1 = jnp.where(p < 4, 0.5 * pf, jnp.where(p < 7, pf - 2.0, 6.0))
    v = jnp.where(t_full != 0, pf, v_e2m1)
    return jnp.where((nib & 0x8) != 0, -v, v)


def _expand_scales(scale_bytes: jax.Array, factor: int):
    """(r, c) scale bytes -> f32 (scale, type) on (r, c * factor) lanes.
    The type rides in the sign through one exact 0/1 MXU expansion."""
    s, t = _decode_scales(scale_bytes)
    st = lanes.expand_lanes(jnp.where(t != 0, -s, s), factor)
    return jnp.abs(st), (st < 0).astype(jnp.int32)


def _expand_weight_tile(wp, ws, bk: int, bn: int):
    """Decode a packed weight tile: payload (bk//2, bn) + scales
    (bk//16, bn//16) -> bf16 (even K rows, odd K rows), each (bk//2, bn),
    with the block scales fused (sans scale32).  Payload row r holds K rows
    2r (low nibble) and 2r+1 (high nibble), both in scale row r // 8."""
    p = wp.astype(jnp.int32)
    s, t = _expand_scales(ws, _G)                      # (bk//16, bn)
    rows = bk // _G

    def down8(a):                                      # each row x 8
        return jnp.broadcast_to(a[:, None, :], (rows, 8, bn)).reshape(
            bk // 2, bn)

    s, t = down8(s), down8(t)
    return tuple((_decode_nibbles(nib, t) * s).astype(jnp.bfloat16)
                 for nib in (p & 0xF, p >> 4))


def _expand_act_tile(xp, xs):
    """Decode packed activations: payload (bm, bk//2) + scales (bm, bk//16)
    -> bf16 (even K, odd K) halves, each (bm, bk//2), block scales fused
    (sans scale32)."""
    p = xp.astype(jnp.int32)
    s, t = _expand_scales(xs, _G // 2)
    return tuple((_decode_nibbles(nib, t) * s).astype(jnp.bfloat16)
                 for nib in (p & 0xF, p >> 4))


def _quantize_act_tile(xe, xo, inv_s32):
    """Fused prologue: quantize a dense f32 x tile, split into even/odd K
    halves, to MixFP4 rows in VMEM (Alg. 1 via the shared
    ``quant_block_kernel_math``) and emit the SAME bf16 values the packed
    decode path reconstructs — ``q`` is exactly decode(encode(q)) on both
    lattices and ``s8`` is already E4M3-valued, so ``(q * s8).astype(bf16)``
    is bitwise what ``_expand_act_tile`` returns for the two-dispatch
    composition."""
    qe, qo, s8, _t = quant_block_kernel_math(xe * inv_s32, xo * inv_s32)
    return (qe * s8).astype(jnp.bfloat16), (qo * s8).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Shared double-buffered kernel body
# ---------------------------------------------------------------------------
def _stream_gemm_body(mode: str, nk: int, bm: int, bn: int, bk: int,
                      per_row: bool, group: int, s32_ref, ws_ref, xs_ref,
                      hbm, slabs, o_ref, acc_ref, sem):
    """Grid cell (i, j): stream K slabs of the operands HBM->VMEM through
    two buffer slots, overlapping the next slab's DMA with the current
    slab's decode + MXU work; the f32 accumulator stays in VMEM scratch and
    is written to the output block once, after the K loop.

    ``hbm``/``slabs`` hold the streamed operands in a fixed order: the
    activation (dense even/odd K halves, or the packed payload in ``w4a4``
    mode), the weight payload, and — fused mode with RHT — the even/odd
    sign halves.  The scale bytes are small; the grid pipeline brings each
    cell's whole K column of them into VMEM (``ws_ref``, and ``xs_ref`` in
    ``w4a4`` mode), indexed by K step.  A K step contracts the even halves
    against the low-nibble weight rows and the odd halves against the
    high-nibble rows.

    ``per_row=True`` reads the scale operand as an (bm, w) row-tile slab
    instead of the (1, w) scalar row: column 0 carries the combined output
    scale (x_row * w per-tensor), column 1 (fused mode) the row's
    activation scale for the prologue, so every output row is scaled by a
    function of that row alone.

    The RHT signs (fused mode only) stream in the activation's K slabs and
    the grouped butterfly (``fwht_split_math``) runs in VMEM ahead of the
    quantizer — the transform is group-local and ``bk % group == 0``, so
    slab-wise application equals the whole-row transform."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    if per_row:
        s32 = s32_ref[...][:, 0:1]          # (bm, 1) combined row scales
    else:
        s32 = s32_ref[0, 0]
    kh = bk // 2
    rows = pl.ds(i * bm, bm)
    n_x = 1 if mode == "w4a4" else 2
    wins = ([lambda kk: (rows, pl.ds(kk * kh, kh))] * n_x
            + [lambda kk: (pl.ds(kk * kh, kh), pl.ds(j * bn, bn))]
            + [lambda kk: (slice(None), pl.ds(kk * kh, kh))] * 2)

    def dmas(slot, kk):
        return [pltpu.make_async_copy(src.at[win(kk)], dst.at[slot],
                                      sem.at[slot, n])
                for n, (src, dst, win) in enumerate(zip(hbm, slabs, wins))]

    for dma in dmas(0, 0):
        dma.start()
    acc_ref[...] = jnp.zeros_like(acc_ref)

    if mode == "w4a4_fused":
        if per_row:
            inv_s32 = 1.0 / s32_ref[...][:, 1:2]   # (bm, 1) row scales
        else:
            inv_s32 = 1.0 / s32_ref[0, 1]   # x per-tensor scale (prologue)

    def body(kk, carry):
        cur = kk % 2
        nxt = (kk + 1) % 2

        @pl.when(kk + 1 < nk)
        def _prefetch():
            for dma in dmas(nxt, kk + 1):
                dma.start()

        for dma in dmas(cur, kk):
            dma.wait()

        if mode == "w4a16":
            xe = slabs[0][cur].astype(jnp.bfloat16)
            xo = slabs[1][cur].astype(jnp.bfloat16)
        elif mode == "w4a4":
            xe, xo = _expand_act_tile(slabs[0][cur], xs_ref[kk])
        else:
            xe, xo = slabs[0][cur], slabs[1][cur]
            if len(slabs) > 3:
                xe, xo = fwht_split_math(xe, xo, slabs[3][cur],
                                         slabs[4][cur], group)
            xe, xo = _quantize_act_tile(xe, xo, inv_s32)
        we, wo = _expand_weight_tile(slabs[n_x][cur], ws_ref[0, kk], bk, bn)
        acc = (jax.lax.dot(xe, we, preferred_element_type=jnp.float32)
               + jax.lax.dot(xo, wo, preferred_element_type=jnp.float32))
        acc_ref[...] += acc * s32
        return carry

    jax.lax.fori_loop(0, nk, body, 0)
    o_ref[...] = acc_ref[...]


def _stream_gemm_call(mode: str, x_args: tuple, s32: jax.Array, payload,
                      scales, m: int, n: int, k: int,
                      bm: int, bn: int, bk: int, interpret: bool,
                      per_row: bool = False,
                      signs: jax.Array | None = None, group: int = _G):
    """``x_args`` is the activation pair: the even/odd K halves (M, K//2)
    of dense rows, or the packed (payload, scales).  Scale bytes are
    re-laid K-tile-major here, outside the kernel (about 1/32 of the
    payload bytes), so a K step's scales are one leading-axis index.  The
    kernel is named after the public entry of its mode
    (``mixfp4_gemm_<mode>``), which names it in a profiler trace."""
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % _G == 0 and bn % _G == 0
    nk = k // bk
    kh = bk // 2
    grid = (m // bm, n // bn)
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    ws = scales.reshape(nk, bk // _G, n // bn, bn // _G).transpose(2, 0, 1, 3)
    in_specs = [
        pl.BlockSpec((1, nk, bk // _G, bn // _G), lambda i, j: (j, 0, 0, 0)),
    ]
    inputs = [ws]
    if mode == "w4a4":
        xp, xs = x_args
        in_specs.append(
            pl.BlockSpec((nk, bm, bk // _G), lambda i, j: (0, i, 0)))
        inputs.append(xs.reshape(m, nk, bk // _G).transpose(1, 0, 2))
        hbm_args = [xp]
        scratch = [pltpu.VMEM((2, bm, kh), jnp.uint8)]
    else:
        hbm_args = list(x_args)
        scratch = [pltpu.VMEM((2, bm, kh), x_args[0].dtype)] * 2
    hbm_args.append(payload)
    scratch.append(pltpu.VMEM((2, kh, bn), jnp.uint8))
    if signs is not None:
        hbm_args += list(lanes.split_even_odd(signs))
        scratch += [pltpu.VMEM((2, 1, kh), jnp.float32)] * 2
    n_hbm = len(hbm_args)
    scratch += [pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.SemaphoreType.DMA((2, n_hbm))]
    if per_row:
        s32_spec = pl.BlockSpec((bm, s32.shape[1]), lambda i, j: (i, 0))
    else:
        s32_spec = pl.BlockSpec(s32.shape, lambda i, j: (0, 0))
    n_vmem = len(inputs)

    def kernel(s32_ref, *refs):
        ws_ref = refs[0]
        xs_ref = refs[1] if mode == "w4a4" else None
        hbm = refs[n_vmem:n_vmem + n_hbm]
        o_ref = refs[n_vmem + n_hbm]
        slabs = refs[n_vmem + n_hbm + 1:n_vmem + 2 * n_hbm + 1]
        acc_ref, sem = refs[n_vmem + 2 * n_hbm + 1:]
        _stream_gemm_body(mode, nk, bm, bn, bk, per_row, group, s32_ref,
                          ws_ref, xs_ref, hbm, slabs, o_ref, acc_ref, sem)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[s32_spec, *in_specs] + [hbm_spec] * n_hbm,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        name=f"mixfp4_gemm_{mode}",
    )(s32, *inputs, *hbm_args)


# ---------------------------------------------------------------------------
# W4A16
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def mixfp4_gemm_w4a16(
    x: jax.Array,
    payload: jax.Array,
    scales: jax.Array,
    scale32: jax.Array,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ dequant(packed W); x (M, K) bf16/f32, returns (M, N) f32."""
    m, k = x.shape
    n = payload.shape[1]
    assert payload.shape == (k // 2, n) and scales.shape == (k // _G, n // _G)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)
    s32 = scale32.reshape(1, 1).astype(jnp.float32)
    xb = x.astype(jnp.bfloat16)     # same single rne rounding as in-kernel
    return _stream_gemm_call(
        "w4a16", lanes.split_even_odd(xb), s32, payload, scales,
        m, n, k, bm, bn, bk, interpret)


# ---------------------------------------------------------------------------
# W4A4 (packed activations: the two-dispatch composition's GEMM half)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "per_row"))
def mixfp4_gemm_w4a4(
    x_payload: jax.Array,
    x_scales: jax.Array,
    x_scale32: jax.Array,
    payload: jax.Array,
    scales: jax.Array,
    scale32: jax.Array,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
    per_row: bool = False,
) -> jax.Array:
    """y = dequant(packed X) @ dequant(packed W), f32 out.

    ``per_row=True`` reads ``x_scale32`` as an (M,) row-scale vector (the
    ``quantize_rows(per_row=True)`` contract): the combined output scale
    becomes an (M, 1) operand tiled with the row grid, so each output row
    is a pure function of its own activation row.
    """
    m = x_payload.shape[0]
    k = x_payload.shape[1] * 2
    n = payload.shape[1]
    assert payload.shape == (k // 2, n) and scales.shape == (k // _G, n // _G)
    assert x_scales.shape == (m, k // _G)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)
    if per_row:
        xs32 = jnp.broadcast_to(
            jnp.asarray(x_scale32, jnp.float32).reshape(-1), (m,))
        s32 = (xs32 * scale32.astype(jnp.float32)).reshape(m, 1)
    else:
        s32 = (x_scale32.astype(jnp.float32)
               * scale32.astype(jnp.float32)).reshape(1, 1)
    return _stream_gemm_call(
        "w4a4", (x_payload, x_scales), s32, payload, scales,
        m, n, k, bm, bn, bk, interpret, per_row=per_row)


# ---------------------------------------------------------------------------
# W4A4 with fused quantize prologue (one dispatch per projection)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "per_row", "rht_group"))
def mixfp4_gemm_w4a4_fused(
    x: jax.Array,
    x_scale32: jax.Array,
    payload: jax.Array,
    scales: jax.Array,
    scale32: jax.Array,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
    per_row: bool = False,
    rht_signs: jax.Array | None = None,
    rht_group: int = _G,
) -> jax.Array:
    """y = dequant(quant(X)) @ dequant(packed W), f32 out — the W4A4 MMA
    with the activation row quantizer fused into the kernel prologue.

    ``x`` is the DENSE (M, K) activation, already zero-padded onto the
    weight's packed K grid (the ``qmm`` dispatcher does this); it is
    quantized tile-by-tile in VMEM under the pinned per-tensor scale
    ``x_scale32`` — which the caller derives exactly as ``quantize_rows``
    would (max|x| / 2688), or pins (KV-cache style) — and the result is
    bitwise-identical to ``quantize_rows(x) -> mixfp4_gemm_w4a4`` run on
    the same (bm, bn, bk) grid.  Zero-padded rows/lanes quantize to zero
    codes and contribute the same exact-zero terms as the composition's
    padded bytes.

    The f32 cast happens HERE, outside the kernel, on purpose: streaming
    bf16 slabs and converting in the prologue is mathematically exact but
    puts a convert inside the kernel body, and XLA's differing fusion of
    that body (vs the standalone quantizer's, which sees f32) can flip
    the dual-format ``err1 < err2`` select at near-ties — observed as a
    non-bitwise MoE stream under ``lax.scan``/``lax.map``.  Halving the
    activation slab traffic is a TPU-side follow-on that needs the select
    pinned first.

    ``per_row=True`` reads ``x_scale32`` as an (M,) row-scale vector — the
    prologue quantizes row i under scale32[i] and the output row is scaled
    by ``scale32[i] * w_scale32``, making it a pure function of activation
    row i (the serve-time batch-independence contract).

    ``rht_signs`` (with ``per_row``) fuses the grouped random Hadamard
    transform (``core.hadamard.rht`` semantics, the ``fwht_split_math``
    butterfly) ahead of the quantizer in the same VMEM pass: signs stream
    in the activation's K slabs, the transform is group-local and
    ``bk % rht_group == 0``, so the result is bitwise what
    ``fwht_rows -> quantize_rows(per_row=True) -> mixfp4_gemm_w4a4`` would
    compute on the same grid.  The caller derives the per-row scale from
    the TRANSFORMED rows (it is the transformed values being quantized)
    and must have applied the same ``D``/``H`` to the packed weight's K
    axis at pack time for the transform to cancel in the dot product.
    """
    m, k = x.shape
    n = payload.shape[1]
    assert payload.shape == (k // 2, n) and scales.shape == (k // _G, n // _G)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)
    signs = None
    if rht_signs is not None:
        if rht_group <= 0 or rht_group & (rht_group - 1):
            raise ValueError(
                f"rht_group must be a power of two, got {rht_group}")
        if bk % rht_group or k % rht_group:
            raise ValueError(
                f"rht_group={rht_group} must divide bk={bk} and K={k} so "
                f"K-slab boundaries align with transform groups")
        if rht_signs.shape != (k,):
            raise ValueError(
                f"rht_signs must have shape ({k},), got {rht_signs.shape}")
        signs = rht_signs.astype(jnp.float32).reshape(1, k)
    if per_row:
        xs32 = jnp.broadcast_to(
            jnp.asarray(x_scale32, jnp.float32).reshape(-1), (m,))
        # (M, 2): [combined output scale, row scale for the prologue]
        s32 = jnp.stack(
            [xs32 * scale32.astype(jnp.float32).reshape(()), xs32], axis=1)
    else:
        xs32 = jnp.asarray(x_scale32, jnp.float32).reshape(())
        # (1, 2): [combined output scale, x per-tensor scale (prologue)]
        s32 = jnp.stack([xs32 * scale32.astype(jnp.float32).reshape(()),
                         xs32]).reshape(1, 2)
    return _stream_gemm_call(
        "w4a4_fused", lanes.split_even_odd(x.astype(jnp.float32)), s32,
        payload, scales, m, n, k, bm, bn, bk, interpret,
        per_row=per_row, signs=signs, group=rht_group)
