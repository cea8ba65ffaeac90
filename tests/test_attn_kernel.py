"""Fused packed-KV decode-attention kernel vs the dequantized reference
(interpret mode): ragged per-slot lengths, GQA grouping, odd dh block
counts, sliding windows, softcaps, and S-padding inside the ops entry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import mixfp4_attn, ops, ref
from repro.models import base


def _packed_kv(key, b, s, hkv, dh, scale=1.0):
    kv = jax.random.normal(key, (b, s, hkv, dh), jnp.float32) * scale
    payload, scales = base.quantize_kv_rows(kv)
    return kv, payload, scales


CASES = [
    # (b, s, hkv, group, dh, window, softcap)
    (2, 32, 2, 2, 32, 0, 0.0),       # GQA, full causal
    (3, 24, 1, 4, 48, 0, 0.0),       # odd dh block count (3 blocks of 16)
    (2, 130, 2, 1, 32, 7, 30.0),     # S padded to the key tile + SWA + cap
    (1, 16, 3, 2, 16, 5, 0.0),       # window, single block of 16 lanes
]


@pytest.mark.parametrize("case", CASES)
def test_attn_decode_matches_dequant_reference(case):
    b, s, hkv, g, dh, window, softcap = case
    h = hkv * g
    keys = jax.random.split(jax.random.PRNGKey(int(sum(case))), 3)
    q = jax.random.normal(keys[0], (b, h, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    lengths = jnp.asarray(
        np.random.RandomState(s).randint(1, s + 1, (b,)), jnp.int32)
    out = ops.attn_decode_packed(q, kp, ks, vp, vs, lengths,
                                 window=window, softcap=softcap,
                                 interpret=True, bs=16)
    want = ref.ref_attn_decode_packed(q, kp, ks, vp, vs, lengths,
                                      window=window, softcap=softcap)
    assert out.shape == (b, h, dh) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_attn_decode_ref_matches_dense_attention():
    """The packed reference itself must agree with the model-side masked
    attention over the dequantized cache (same decode semantics: query at
    position lengths-1, kv_valid_len=lengths)."""
    b, s, hkv, g, dh = 2, 24, 2, 2, 32
    h = hkv * g
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, h, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    lengths = jnp.asarray([5, 17], jnp.int32)
    got = ref.ref_attn_decode_packed(q, kp, ks, vp, vs, lengths)
    k = ref.ref_dequant_kv(kp, ks)
    v = ref.ref_dequant_kv(vp, vs)
    want = base.attention(q[:, None].astype(jnp.float32), k, v,
                          causal_offset=lengths - 1,
                          kv_valid_len=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=1e-5)


def test_attn_decode_full_vs_length_one():
    """lengths=1 attends only to the single valid row: the output is that
    row's V (softmax over one key), for every head group."""
    b, s, hkv, dh = 1, 16, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, 2 * hkv, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    v, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    out = ops.attn_decode_packed(q, kp, ks, vp, vs,
                                 jnp.ones((b,), jnp.int32), interpret=True)
    vrow = np.asarray(ref.ref_dequant_kv(vp, vs))[:, 0]  # (b, hkv, dh)
    want = np.repeat(vrow, 2, axis=1)                    # groups share kv
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


def test_quantize_kv_rows_pinned_scale32_roundtrip():
    """Incremental writes: quantizing rows one at a time under the shared
    KV_SCALE32 must produce the exact bytes of quantizing them all at
    once (that is what makes batched prefill == replay on packed rows)."""
    kv = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 2, 32)) * 0.8
    p_all, s_all = base.quantize_kv_rows(kv)
    for t in range(6):
        p_t, s_t = base.quantize_kv_rows(kv[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(p_all[:, t:t + 1]),
                                      np.asarray(p_t))
        np.testing.assert_array_equal(np.asarray(s_all[:, t:t + 1]),
                                      np.asarray(s_t))


# ---------------------------------------------------------------------------
# Paged decode: block-table pool slabs vs the fixed-slot kernel (PR-6)
# ---------------------------------------------------------------------------
def _page_slabs(payload, scales, page_len, seed):
    """Scatter a fixed (B, S, ...) packed cache into randomly-permuted pool
    slabs (P, page_len, ...) + the block tables that map them back.  Page 0
    stays zeroed — the pool's trash page."""
    b, s = payload.shape[:2]
    mp = s // page_len
    bt = 1 + np.random.RandomState(seed).permutation(b * mp).reshape(b, mp)
    slab_p = np.zeros((1 + b * mp, page_len) + payload.shape[2:],
                      np.asarray(payload).dtype)
    slab_s = np.zeros((1 + b * mp, page_len) + scales.shape[2:],
                      np.asarray(scales).dtype)
    for i in range(b):
        for j in range(mp):
            sl = slice(j * page_len, (j + 1) * page_len)
            slab_p[bt[i, j]] = payload[i, sl]
            slab_s[bt[i, j]] = scales[i, sl]
    return jnp.asarray(slab_p), jnp.asarray(slab_s), jnp.asarray(bt, jnp.int32)


PAGED_CASES = [
    # (b, s, hkv, group, dh, window, softcap, page_len, bs)
    (2, 64, 2, 2, 32, 0, 0.0, 16, 16),      # bs == page_len
    (2, 64, 2, 2, 32, 0, 0.0, 32, 16),      # bs < page_len: sub-page blocks
    (2, 64, 1, 4, 48, 7, 30.0, 16, 32),     # bs > page_len + SWA + softcap
    (1, 32, 2, 1, 32, 0, 0.0, 16, None),    # tuner-default key block
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_attn_decode_paged_bitwise_matches_fixed(case):
    """Acceptance: the paged kernel over permuted pool slabs must be
    BITWISE-identical to the fixed-slot kernel on the same logical rows —
    the block-table gather happens in BlockSpec index maps, the flash body
    is shared, and a matched key-block size means the same reduction
    order."""
    b, s, hkv, g, dh, window, softcap, page_len, bs = case
    h = hkv * g
    keys = jax.random.split(jax.random.PRNGKey(int(sum(case[:7]))), 3)
    q = jax.random.normal(keys[0], (b, h, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    lengths = jnp.asarray(
        np.random.RandomState(s).randint(1, s + 1, (b,)), jnp.int32)
    fixed = ops.attn_decode_packed(q, kp, ks, vp, vs, lengths,
                                   window=window, softcap=softcap,
                                   interpret=True, bs=bs)
    kpp, kps, bt = _page_slabs(kp, ks, page_len, seed=s)
    vpp, vps, bt2 = _page_slabs(vp, vs, page_len, seed=s)
    np.testing.assert_array_equal(np.asarray(bt), np.asarray(bt2))
    paged = ops.attn_decode_paged(q, kpp, kps, vpp, vps, bt, lengths,
                                  window=window, softcap=softcap,
                                  interpret=True, bs=bs)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(fixed))


def test_attn_decode_paged_matches_ref_gather():
    """The paged reference (gather logical view, then the dequant oracle)
    agrees with the paged kernel to f32 tolerance — an independent check
    that the index maps really read the pages the table names."""
    b, s, hkv, g, dh, page_len = 2, 48, 2, 2, 32, 16
    h = hkv * g
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (b, h, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    kpp, kps, bt = _page_slabs(kp, ks, page_len, seed=7)
    vpp, vps, _ = _page_slabs(vp, vs, page_len, seed=7)
    lengths = jnp.asarray([33, 48], jnp.int32)
    got = ops.attn_decode_paged(q, kpp, kps, vpp, vps, bt, lengths,
                                interpret=True, bs=16)
    want = ref.ref_attn_decode_packed(q, kpp, kps, vpp, vps, lengths,
                                      block_tables=bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_attn_decode_paged_trash_page_masked():
    """Rows in trailing trash-page table entries (page 0) must never leak
    into the output: a table whose tail columns point at a garbage-filled
    page 0 gives the same result as one pointing at real-but-masked
    pages."""
    b, s, hkv, dh, page_len = 1, 32, 2, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (b, 2 * hkv, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    kpp, kps, bt = _page_slabs(kp, ks, page_len, seed=3)
    vpp, vps, _ = _page_slabs(vp, vs, page_len, seed=3)
    lengths = jnp.asarray([13], jnp.int32)   # only page 1 of 2 is valid
    base_out = ops.attn_decode_paged(q, kpp, kps, vpp, vps, bt, lengths,
                                     interpret=True, bs=16)
    # fill the trash page with junk WIRE bytes (an unrelated quantized
    # cache: inactive-lane scatters write real encoder output, never
    # arbitrary bit patterns) and point the tail column at it
    _, jp, js = _packed_kv(jax.random.PRNGKey(99), 1, page_len, hkv, dh,
                           scale=3.0)
    kpp = kpp.at[0].set(jp[0])
    vpp = vpp.at[0].set(jp[0])
    kps = kps.at[0].set(js[0])
    vps = vps.at[0].set(js[0])
    bt_trash = jnp.asarray(np.array([[int(bt[0, 0]), 0]]), jnp.int32)
    out = ops.attn_decode_paged(q, kpp, kps, vpp, vps, bt_trash, lengths,
                                interpret=True, bs=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base_out))


# ---------------------------------------------------------------------------
# Key-block range: each row visits only the blocks that hold a key it
# attends to (no DMA, no decode of the rest)
# ---------------------------------------------------------------------------
def _paged_and_fixed(b, s, hkv, g, dh, window, page_len, bs, lengths,
                     softcap=0.0, seed=0):
    """(paged, fixed) outputs over the same logical rows, and the inputs."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, hkv * g, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    lengths = jnp.asarray(lengths, jnp.int32)
    fixed = ops.attn_decode_packed(q, kp, ks, vp, vs, lengths,
                                   window=window, softcap=softcap,
                                   interpret=True, bs=bs)
    kpp, kps, bt = _page_slabs(kp, ks, page_len, seed=seed)
    vpp, vps, _ = _page_slabs(vp, vs, page_len, seed=seed)
    paged = ops.attn_decode_paged(q, kpp, kps, vpp, vps, bt, lengths,
                                  window=window, softcap=softcap,
                                  interpret=True, bs=bs)
    return paged, fixed, (q, kp, ks, vp, vs, lengths)


EDGE_CASES = [
    # (s, page_len, bs): bs == page, bs < page (sub-page), bs > page
    (64, 16, 16),
    (64, 32, 16),
    (96, 16, 32),
]


@pytest.mark.parametrize("s,page_len,bs", EDGE_CASES)
@pytest.mark.parametrize("window", [0, 5])
def test_attn_decode_paged_bitwise_at_block_edges(s, page_len, bs, window):
    """Lengths on either side of a key-block edge (1, bs-1, bs, bs+1, S):
    the last block a row visits is partly or wholly valid, and the paged
    kernel stays bitwise the fixed-slot one and close to the oracle."""
    lengths = [1, bs - 1, bs, bs + 1, s]
    paged, fixed, args = _paged_and_fixed(5, s, 2, 2, 32, window, page_len,
                                          bs, lengths, seed=s + bs)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(fixed))
    want = ref.ref_attn_decode_packed(*args, window=window)
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("page_len,bs", [(16, 16), (32, 16), (16, 32)])
def test_attn_decode_window_skips_whole_blocks(page_len, bs):
    """A window that starts several key blocks into the row: the blocks
    before it are skipped, and the result is the oracle's and bitwise
    the fixed-slot kernel's."""
    window, lengths = 20, [100, 128, 57, 21]
    assert all(l - window >= bs for l in lengths[:3])
    paged, fixed, args = _paged_and_fixed(4, 128, 2, 2, 32, window,
                                          page_len, bs, lengths,
                                          softcap=30.0, seed=bs)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(fixed))
    want = ref.ref_attn_decode_packed(*args, window=window, softcap=30.0)
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(want),
                               atol=1e-5)


def _random_tail(a, lengths, bs, scale_bytes: bool, rng):
    """``a`` (B, S, ...) with every row at or past each length replaced by
    random non-zero bytes.  Scale bytes in the last block a row visits
    avoid E4M3's NaN code (0x7F under the type bit): those rows are
    decoded and multiplied by a zero weight, and only the quantizer
    writes them.  Blocks wholly past the length may hold NaN codes: the
    kernel never decodes them."""
    a = np.array(a)
    for i, n in enumerate(lengths):
        tail = rng.integers(1, 256, a[i, n:].shape).astype(np.uint8)
        if scale_bytes:
            edge = min(-(-n // bs) * bs, a.shape[1]) - n
            part = tail[:edge]
            part[(part & 0x7F) == 0x7F] -= 1
            tail[edge:].flat[::7] = 0x7F
        a[i, n:] = tail
    return jnp.asarray(a)


@pytest.mark.parametrize("page_len,bs", [(16, 16), (32, 16), (16, 32)])
def test_attn_decode_ignores_rows_past_length(page_len, bs):
    """Rows past each length filled with random bytes read bitwise as if
    they were zeros, on both kernels: rows inside a visited block carry a
    zero weight, and blocks past the last one are never decoded."""
    b, s, hkv, g, dh = 4, 128, 2, 2, 32
    lengths = [1, bs + 3, 3 * bs, 70]
    keys = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(keys[0], (b, hkv * g, dh), jnp.float32)
    _, kp, ks = _packed_kv(keys[1], b, s, hkv, dh)
    _, vp, vs = _packed_kv(keys[2], b, s, hkv, dh)
    rng = np.random.default_rng(bs + page_len)
    valid = (np.arange(s)[None, :] < np.asarray(lengths)[:, None])
    zeros = [jnp.where(valid[..., None, None], a, 0)
             for a in (kp, ks, vp, vs)]
    junk = [_random_tail(a, lengths, bs, k % 2 == 1, rng)
            for k, a in enumerate((kp, ks, vp, vs))]
    ln = jnp.asarray(lengths, jnp.int32)
    outs = []
    for kv in (zeros, junk):
        fixed = ops.attn_decode_packed(q, *kv, ln, interpret=True, bs=bs)
        slabs = [_page_slabs(kv[k], kv[k + 1], page_len, seed=5)
                 for k in (0, 2)]
        paged = ops.attn_decode_paged(q, *slabs[0][:2], *slabs[1][:2],
                                      slabs[0][2], ln, interpret=True,
                                      bs=bs)
        outs.append((np.asarray(fixed), np.asarray(paged)))
    assert np.isfinite(outs[0][0]).all()
    for got in outs[1]:
        np.testing.assert_array_equal(got, outs[0][0])
    np.testing.assert_array_equal(outs[0][1], outs[0][0])


@pytest.mark.parametrize("bs", [16, 32, 48])
def test_key_block_range_is_the_masked_blocks(bs):
    """Brute force over lengths and windows: a block lies in
    ``[first, last]`` iff some key in it passes ``_flash_step``'s mask
    (row below the length, and inside the window); a row of no keys
    reads (0, 0).  numpy and traced int32 give the same range."""
    nb = 5
    kpos = np.arange(nb * bs)
    lengths = np.arange(-1, nb * bs + 1)
    windows = np.array([0, 1, 2, bs - 1, bs, bs + 1, 2 * bs + 3, 10 * bs])
    ln, wn = np.meshgrid(lengths, windows, indexing="ij")
    first, last = mixfp4_attn.key_block_range(ln, wn, bs, nb, xp=np)
    jfirst, jlast = jax.jit(
        lambda a, w: mixfp4_attn.key_block_range(a, w, bs, nb))(ln, wn)
    np.testing.assert_array_equal(np.asarray(jfirst), first)
    np.testing.assert_array_equal(np.asarray(jlast), last)
    for (n, w), lo, hi in zip(np.ndindex(ln.shape), first.flat, last.flat):
        length, win = lengths[n], windows[w]
        keep = (kpos < length) & ((kpos > length - 1 - win) | (win <= 0))
        hit = keep.reshape(nb, bs).any(axis=1)
        if length <= 0:
            assert (lo, hi) == (0, 0) and not hit.any()
            continue
        np.testing.assert_array_equal(
            hit, (np.arange(nb) >= lo) & (np.arange(nb) <= hi),
            err_msg=f"length {length} window {win}")


@pytest.mark.parametrize("page_len,max_pages", [(16, 64), (16, 8), (64, 4)])
def test_paged_kv_blocks_counts_the_blocks_the_kernel_reads(page_len,
                                                            max_pages):
    """The host count of key blocks visited equals the number of distinct
    key blocks the paged kernel's unit table names per row (each table
    entry a distinct page), for whole-page and sub-page key blocks, over
    lengths at the block edges and windows that drop whole blocks."""
    hkv, dh = 2, 32
    bs = mixfp4_attn.paged_key_block(max_pages, page_len, hkv, dh)
    s = max_pages * page_len
    nb = -(-s // bs)
    unit = min(bs, page_len)
    n_sub, upp = bs // unit, page_len // unit
    lengths = np.array([0, 1, bs - 1, bs, bs + 1, s // 2, s - 1, s])
    tables = jnp.asarray(1 + np.arange(len(lengths) * max_pages).reshape(
        len(lengths), max_pages), jnp.int32)
    for win in (0, 1, bs + 3, 2 * bs + 1):
        units = np.asarray(mixfp4_attn._visited_units(
            tables, jnp.asarray(lengths, jnp.int32), jnp.int32(win), bs, nb,
            n_sub, upp)).reshape(len(lengths), nb, n_sub)
        want = sum(len({tuple(u) for u in row}) for row in units)
        assert mixfp4_attn.paged_kv_blocks(
            lengths, [win], max_pages, page_len, hkv, dh) == want, win
