"""Pallas TPU kernel: fused decode attention over a packed MixFP4 KV cache.

The serving engine's dominant decode traffic term is the KV cache read
(ROADMAP "decode_32k").  Holding the cache in the paper's wire format
(4-bit payload + type-in-sign E4M3 scale bytes, 4.5 bits/value) only pays
off if the packed representation is consumed *directly* by the attention
read — dequantizing the whole cache back to bf16 in HBM before every step
would spend the saved bandwidth immediately.  This kernel streams the
packed K/V blocks HBM->VMEM, runs the same branch-free Fig. 9 dual-codebook
decode as ``mixfp4_gemm`` (shared ``_decode_nibbles``/``_expand_scales``)
on 16-lane blocks in VMEM, and computes masked online-softmax attention
(flash-decoding) for one query token per sequence.  No dense bf16 copy of
the cache ever exists in HBM.

Layout (matches the 1-D ``BlockLayout1D(-1, 16)`` QTensor KV cache built by
``models.transformer.init_cache(kv_quant="mixfp4")``):

  q          (B, H, dh)          bf16/f32 — the RoPE'd decode-step query
  k/v payload(B, S, Hkv, dh//2)  uint8    — two dh-consecutive nibbles/byte
  k/v scales (B, S, Hkv, dh//16) uint8    — {T | e4m3[6:0]} per 16-lane block
  lengths    (B,)                int32    — valid rows per sequence
                                           (the current token's row included)

Grid: (B, S/bs) with the key-block loop innermost; the running
(max, sum, acc) flash state lives in VMEM scratch across the key loop and
the output row is emitted on the last block.  The kernel sees a key block
as 2-D (bs, Hkv*dh//2) bytes (a free reshape of the cache) whose low and
high nibbles are the even and odd head dims; the queries arrive split the
same way and block-diagonal over kv heads, so each kv head's packed
blocks are decoded exactly once per step and every product is a 2-D
matmul.  The lengths, the window and the block table ride scalar
prefetch, the two per-tensor scales SMEM.  Masking covers ragged per-slot
lengths, sliding windows and the S padding the ``ops`` entry may add;
``softcap`` is a compile-time constant (it is an arch property, not a
per-layer one).

``mixfp4_attn_decode_paged`` is the same flash loop over a *paged* pool
(``serving/kvpool.py``): K/V slabs are (P, page_len, Hkv, ...) physical
pages and a per-sequence block table maps logical key-block j to physical
pages via ``pltpu.PrefetchScalarGridSpec`` scalar prefetch — the block
table is read at *index-map* time, so each grid step DMAs exactly the
pages it needs and the kernel body never sees the indirection.  Both
kernels share ``_flash_step``, so a paged read over the same logical rows
runs literally the same arithmetic as the fixed-slot kernel (the bitwise
paged==fixed contract the serving tests pin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes
from repro.kernels.mixfp4_gemm import _decode_nibbles, _expand_scales

__all__ = ["mixfp4_attn_decode", "mixfp4_attn_decode_paged"]

_G = 16
_NEG_INF = -1e30


def _decode_kv_block(payload, scales):
    """(bs, Hkv*dh//2) packed + (bs, Hkv*dh//16) scale bytes -> bf16
    (even dims, odd dims), each (bs, Hkv*dh//2), with the block scales
    fused (sans the per-tensor scale, so every value — a 4-bit code times
    an E4M3 scale — is exact in bf16).  Lane ``h*dh//2 + i`` holds dims 2i
    and 2i+1 of kv head h, and its scale byte is lane
    ``(h*dh//2 + i) // 8``."""
    p = payload.astype(jnp.int32)
    s, t = _expand_scales(scales, _G // 2)
    return tuple((_decode_nibbles(nib, t) * s).astype(jnp.bfloat16)
                 for nib in (p & 0xF, p >> 4))


def _dot_f32_bf16(a, b, dims):
    """``a @ b`` for f32 ``a`` and bf16-exact ``b`` as two bf16 MXU passes
    (``a``'s high and low bf16 halves), f32-accurate to about 2^-16."""
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    return dot(hi, b, dims) + dot(lo, b, dims)


def _flash_step(qe_ref, qo_ref, kp, ks, vp, vs, kv_len, win, s32_ref,
                oe_ref, oo_ref, acce_ref, acco_ref, m_ref, l_ref, *,
                softcap: float, dh: int):
    """One key-block step of the flash-decoding loop, shared verbatim by
    the fixed-slot and paged kernels: decode the packed (bs, Hkv*dh//2)
    K/V block, fold it into the running (max, sum, acc) scratch state, and
    emit the normalized output rows on the last block.  Keeping both
    kernels on this one body is what makes paged==fixed a *bitwise*
    contract rather than an allclose one.

    The queries arrive block-diagonal: row r holds query head r's even /
    odd dims in the lanes of its kv head and zeros elsewhere, so one 2-D
    matmul scores every head against its own kv head.  The P·V product
    fills every kv head's lanes of each row; the caller keeps row r's own
    head."""
    s_idx = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        acce_ref[...] = jnp.zeros_like(acce_ref)
        acco_ref[...] = jnp.zeros_like(acco_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    bs = kp.shape[0]
    dot = _dot_f32_bf16
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))
    # the per-tensor scales multiply the scores and the emitted rows
    ke, ko = _decode_kv_block(kp, ks)                          # (bs, W)
    s = (dot(qe_ref[0], ke, nt) + dot(qo_ref[0], ko, nt)
         ) * (s32_ref[0] * dh ** -0.5)                         # (H, bs)
    if softcap:
        s = softcap * jnp.tanh(s * (1.0 / softcap))

    # decode-position masking: the query sits at position kv_len - 1
    kpos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = kpos < kv_len
    mask &= (kpos > kv_len - 1 - win) | (win <= 0)
    s = jnp.where(mask, s, _NEG_INF)

    # online-softmax update (flash-decoding running state in scratch)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    ve, vo = _decode_kv_block(vp, vs)
    acce_ref[...] = acce_ref[...] * alpha + dot(p, ve, nn)
    acco_ref[...] = acco_ref[...] * alpha + dot(p, vo, nn)

    @pl.when(s_idx == ns - 1)
    def _emit():
        l = l_ref[...]
        l = jnp.where(l > 0, l, 1.0)
        oe_ref[0] = acce_ref[...] / l * s32_ref[1]
        oo_ref[0] = acco_ref[...] / l * s32_ref[1]


def _split_queries(q: jax.Array, hkv: int):
    """(B, H, dh) -> block-diagonal even/odd query rows (B, H, Hkv*dh//2)
    and the kv head of each query head."""
    b, h, dh = q.shape
    head = jnp.arange(h) // (h // hkv)
    onehot = (head[:, None] == jnp.arange(hkv)[None, :]).astype(jnp.float32)
    return tuple(
        (half[:, :, None, :] * onehot[None, :, :, None]).reshape(
            b, h, hkv * dh // 2)
        for half in lanes.split_even_odd(q.astype(jnp.float32))), head


def _merge_heads(oe, oo, head, hkv: int):
    """Keep each query row's own kv-head lanes and re-interleave dims."""
    b, h, w = oe.shape

    def own(o):
        o = o.reshape(b, h, hkv, w // hkv)
        return jnp.take_along_axis(o, head[None, :, None, None], 2)[:, :, 0]

    return lanes.merge_even_odd(own(oe), own(oo))


def _scalars(lengths, window, k_scale32, v_scale32, b: int):
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    win = jnp.asarray(window, jnp.int32).reshape(1)
    s32 = jnp.stack([jnp.asarray(k_scale32, jnp.float32).reshape(()),
                     jnp.asarray(v_scale32, jnp.float32).reshape(())])
    return lengths, win, s32


def _flat_kv(a: jax.Array) -> jax.Array:
    """(..., S, Hkv, x) -> (..., S, Hkv*x): a free row-major reshape."""
    return a.reshape(*a.shape[:-2], a.shape[-2] * a.shape[-1])


def _call(name, kernel, grid, n_prefetch, kv_specs, q_pair, h, w, interpret,
          scalars, kv_args):
    """The pallas_call both kernels share, named ``name`` (the public
    kernel's name, which a profiler trace shows).  ``scalars`` is the
    ``n_prefetch`` scalar-prefetch operands (block table, lengths, window)
    then the two K/V scales, which go to SMEM; then come the block-
    diagonal query halves and the packed K/V operands.  Outputs are the
    even/odd halves, with the flash state in VMEM scratch."""
    qmap = lambda i, j, *_: (i, 0, 0)
    q_spec = pl.BlockSpec((1, h, w), qmap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, q_spec,
                  *kv_specs],
        out_specs=[q_spec, q_spec],
        scratch_shapes=[
            pltpu.VMEM((h, w), jnp.float32),    # acc, even dims
            pltpu.VMEM((h, w), jnp.float32),    # acc, odd dims
            pltpu.VMEM((h, 1), jnp.float32),    # running max
            pltpu.VMEM((h, 1), jnp.float32),    # running sum
        ],
    )
    out = jax.ShapeDtypeStruct((q_pair[0].shape[0], h, w), jnp.float32)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=[out, out],
        interpret=interpret, name=name,
    )(*scalars, *q_pair, *kv_args)


def _attn_decode_kernel(len_ref, win_ref, s32_ref, qe_ref, qo_ref,
                        kp_ref, ks_ref, vp_ref, vs_ref, *refs,
                        softcap: float, dh: int):
    _flash_step(qe_ref, qo_ref, kp_ref[0], ks_ref[0], vp_ref[0], vs_ref[0],
                len_ref[pl.program_id(0)], win_ref[0], s32_ref, *refs,
                softcap=softcap, dh=dh)


def _attn_decode_paged_kernel(bt_ref, len_ref, win_ref, s32_ref, qe_ref,
                              qo_ref, *refs, softcap: float, dh: int,
                              n_sub: int):
    """Paged flash step: the grid's index maps already gathered the right
    physical pages (via the prefetched block table), so the body only has
    to stitch the ``n_sub`` page-sized sub-blocks back into one logical
    (bs, Hkv*dh//2) key block.  Packed bytes concatenate before decode ==
    decode-then-concatenate (the Fig. 9 decode is element-wise per row)."""
    del bt_ref  # consumed by the index maps
    kv, out = refs[:4 * n_sub], refs[4 * n_sub:]

    def cat(sub_refs):
        blocks = [r[0].astype(jnp.int32) for r in sub_refs]
        return blocks[0] if n_sub == 1 else jnp.concatenate(blocks, axis=0)

    kp = cat(kv[0 * n_sub:1 * n_sub])
    ks = cat(kv[1 * n_sub:2 * n_sub])
    vp = cat(kv[2 * n_sub:3 * n_sub])
    vs = cat(kv[3 * n_sub:4 * n_sub])
    _flash_step(qe_ref, qo_ref, kp, ks, vp, vs, len_ref[pl.program_id(0)],
                win_ref[0], s32_ref, *out, softcap=softcap, dh=dh)


@functools.partial(
    jax.jit, static_argnames=("softcap", "bs", "interpret"))
def mixfp4_attn_decode(
    q: jax.Array,
    k_payload: jax.Array,
    k_scales: jax.Array,
    v_payload: jax.Array,
    v_scales: jax.Array,
    lengths: jax.Array,
    *,
    window: jax.Array | int = 0,
    k_scale32: jax.Array | float = 1.0,
    v_scale32: jax.Array | float = 1.0,
    softcap: float = 0.0,
    bs: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One decode-attention step over the packed KV cache -> (B, H, dh) f32.

    ``lengths`` counts the valid cache rows per sequence (including the
    current token's just-written row); ``window`` (0 = full causal) and the
    per-tensor scales are dynamic operands so the per-layer ``lax.scan`` in
    the model can trace them.  S is padded to a multiple of the key-block
    tile here; padded rows are masked, so callers never pad.  ``bs=None``
    asks the cost-model tuner (``kernels.tuning.select_attn_key_block``)
    for the key-block rows per flash step — sized against the same VMEM /
    traffic model the GEMM tiles use.
    """
    b, h, dh = q.shape
    s, hkv, dh2 = k_payload.shape[1:]
    assert dh == 2 * dh2, f"q dh={dh} vs packed payload dh={2 * dh2}"
    assert dh % _G == 0, f"dh={dh} must be a multiple of {_G}"
    assert h % hkv == 0, f"H={h} not a multiple of Hkv={hkv}"
    assert k_scales.shape == (b, s, hkv, dh // _G)

    if bs is None:
        from repro.kernels import tuning  # deferred: keep module deps flat
        bs = tuning.select_attn_key_block(s, hkv, dh)
    bs = min(bs, max(s, 1))
    sp = -(-s // bs) * bs
    kv = [_flat_kv(a) for a in (k_payload, k_scales, v_payload, v_scales)]
    if sp != s:  # padded rows are masked by `kpos < lengths`
        kv = [jnp.pad(a, ((0, 0), (0, sp - s), (0, 0))) for a in kv]

    w = hkv * dh2
    kv_spec = pl.BlockSpec((1, bs, w), lambda i, j, *_: (i, j, 0))
    sc_spec = pl.BlockSpec((1, bs, w // 8), lambda i, j, *_: (i, j, 0))
    (qe, qo), head = _split_queries(q, hkv)
    lengths, win, s32 = _scalars(lengths, window, k_scale32, v_scale32, b)
    oe, oo = _call(
        "mixfp4_attn_decode",
        functools.partial(_attn_decode_kernel, softcap=softcap, dh=dh),
        (b, sp // bs), 2, [kv_spec, sc_spec, kv_spec, sc_spec], (qe, qo),
        h, w, interpret, (lengths, win, s32), kv)
    return _merge_heads(oe, oo, head, hkv)


@functools.partial(
    jax.jit, static_argnames=("softcap", "bs", "interpret"))
def mixfp4_attn_decode_paged(
    q: jax.Array,
    k_payload: jax.Array,
    k_scales: jax.Array,
    v_payload: jax.Array,
    v_scales: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    window: jax.Array | int = 0,
    k_scale32: jax.Array | float = 1.0,
    v_scale32: jax.Array | float = 1.0,
    softcap: float = 0.0,
    bs: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over the *paged* packed KV pool -> (B, H, dh) f32.

    K/V children are physical page slabs ``(P, page_len, Hkv, ...)`` and
    ``block_tables`` (B, max_pages) int32 maps each sequence's logical page
    order to slab rows (page 0 is the pool's trash page: unused table tail
    entries point there and are masked by ``lengths``).  The table rides
    ``PrefetchScalarGridSpec`` scalar prefetch so the page gather happens
    in the BlockSpec index maps — per grid step the kernel DMAs only the
    pages of that key block, and the body is the same ``_flash_step`` as
    the fixed-slot kernel.  With ``bs`` equal to the fixed path's tuner
    choice for the same logical S (the serving engine guarantees this by
    requiring ``max_len % page_len == 0``), the paged output is
    bitwise-identical to ``mixfp4_attn_decode`` on the gathered rows.
    """
    b, h, dh = q.shape
    n_pages, page_len, hkv, dh2 = k_payload.shape
    assert dh == 2 * dh2, f"q dh={dh} vs packed payload dh={2 * dh2}"
    assert dh % _G == 0, f"dh={dh} must be a multiple of {_G}"
    assert page_len % _G == 0, f"page_len={page_len} not a multiple of {_G}"
    assert h % hkv == 0, f"H={h} not a multiple of Hkv={hkv}"
    assert k_scales.shape == (n_pages, page_len, hkv, dh // _G)
    assert block_tables.ndim == 2 and block_tables.shape[0] == b

    max_pages = block_tables.shape[1]
    s_logical = max_pages * page_len
    if bs is None:
        from repro.kernels import tuning  # deferred: keep module deps flat
        bs = tuning.select_attn_key_block(s_logical, hkv, dh)
    bs = min(bs, max(s_logical, 1))
    # The grid needs bs and page_len commensurate so each key block is a
    # whole number of (partial) pages; power-of-two page lengths always
    # satisfy this for the tuner's power-of-two bs choices.
    if bs >= page_len:
        bs -= bs % page_len
    elif page_len % bs:
        bs = page_len

    sp = -(-s_logical // bs) * bs
    if sp != s_logical:  # pad table columns with the trash page (masked)
        block_tables = jnp.pad(
            block_tables, ((0, 0), (0, sp // page_len - max_pages)))
    block_tables = jnp.asarray(block_tables, jnp.int32)

    if bs >= page_len:
        n_sub, rows = bs // page_len, page_len

        def _page_map(t):
            return lambda i, j, bt, *_: (bt[i, j * n_sub + t], 0, 0)

        maps = [_page_map(t) for t in range(n_sub)]
    else:
        n_sub, rows = 1, bs
        ipb = page_len // bs
        maps = [lambda i, j, bt, *_: (bt[i, j // ipb], j % ipb, 0)]

    w = hkv * dh2
    kv_specs = [pl.BlockSpec((1, rows, w), m) for m in maps]
    sc_specs = [pl.BlockSpec((1, rows, w // 8), m) for m in maps]
    kp, ks, vp, vs = (_flat_kv(a)
                      for a in (k_payload, k_scales, v_payload, v_scales))
    (qe, qo), head = _split_queries(q, hkv)
    lengths, win, s32 = _scalars(lengths, window, k_scale32, v_scale32, b)
    oe, oo = _call(
        "mixfp4_attn_decode_paged",
        functools.partial(_attn_decode_paged_kernel, softcap=softcap,
                          dh=dh, n_sub=n_sub),
        (b, sp // bs), 3, [*kv_specs, *sc_specs, *kv_specs, *sc_specs],
        (qe, qo), h, w, interpret, (block_tables, lengths, win, s32),
        [kp] * n_sub + [ks] * n_sub + [vp] * n_sub + [vs] * n_sub)
    return _merge_heads(oe, oo, head, hkv)
