"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as Q
from repro.kernels import ops, ref
from repro.kernels.mixfp4_quant import mixfp4_quant_rows


QUANT_SHAPES = [(8, 32), (16, 128), (64, 64), (128, 256), (4, 1024)]


@pytest.mark.parametrize("shape", QUANT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_kernel_bit_exact(shape, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(shape[0] * shape[1]), shape)
         * 3.0).astype(dtype)
    p_k, s_k, s32_k = mixfp4_quant_rows(x.astype(jnp.float32),
                                        interpret=True)
    p_r, s_r, s32_r = ref.ref_quant_pack_rows(x.astype(jnp.float32), "mixfp4")
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_allclose(float(s32_k), float(s32_r), rtol=1e-6)


@pytest.mark.parametrize("tile", [(8, 16, 16), (16, 32, 64)])
@pytest.mark.parametrize("mkn", [(16, 64, 32), (32, 128, 64), (64, 256, 128)])
def test_gemm_w4a16_sweep(mkn, tile):
    m, k, n = mkn
    bm, bn, bk = tile
    if m % bm or n % bn or k % bk:
        pytest.skip("tile must divide problem")
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n), jnp.float32) * 0.3
    qw = ops.pack_weight_qt(w)
    payload, scales, s32 = qw.payload, qw.scales, qw.scale32
    y_k = ops.gemm_w4a16(x, payload, scales, s32, bm=bm, bn=bn, bk=bk,
                         interpret=True)
    # f32 oracle (no bf16 tile rounding): dequantized weight matmul
    wd = ref.ref_dequant_weight_kn(payload, scales, s32)
    y_f32 = x @ wd
    # tolerance: bf16 operand rounding ~2^-8 relative
    scale = float(jnp.abs(y_f32).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y_k) / scale,
                               np.asarray(y_f32) / scale, atol=2e-2)


def test_gemm_w4a16_dequant_matches_qdq2d():
    """The packed weight path must represent exactly qdq_2d's values."""
    w = jax.random.normal(jax.random.PRNGKey(3), (96, 48)) * 0.5
    qw = ops.pack_weight_qt(w)
    wd = ref.ref_dequant_weight_kn(qw.payload, qw.scales, qw.scale32)
    wq = Q.qdq_2d(w, "mixfp4")
    np.testing.assert_allclose(np.asarray(wd), np.asarray(wq), rtol=0, atol=0)


@pytest.mark.parametrize("mkn", [(16, 64, 32), (32, 128, 64)])
def test_gemm_w4a4_sweep(mkn):
    m, k, n = mkn
    x = jax.random.normal(jax.random.PRNGKey(4), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (k, n), jnp.float32) * 0.3
    qw = ops.pack_weight_qt(w)
    payload, scales, s32 = qw.payload, qw.scales, qw.scale32
    xp, xs, xs32 = ops.quantize_rows(x, interpret=True)
    y_k = ops.gemm_w4a4(xp, xs, xs32, payload, scales, s32,
                        bm=8, bn=16, bk=32, interpret=True)
    y_r = ref.ref_gemm_w4a4(xp, xs, xs32, payload, scales, s32)
    scale = float(jnp.abs(y_r).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y_k) / scale,
                               np.asarray(y_r) / scale, atol=2e-2)


@pytest.mark.parametrize("mkn", [(16, 64, 32), (5, 40, 24), (8, 272, 144)])
def test_gemm_w4a4_fused_bitwise_vs_composition(mkn):
    """The fused quantize+GEMM prologue must reproduce the two-dispatch
    ``quantize_rows -> qmm`` composition BIT FOR BIT (same tuner grid,
    exact encode/decode round trip in the prologue) — incl. K/N padding
    onto the packed grid and non-round dims the tuner pads further."""
    from repro.core import qtensor
    m, k, n = mkn
    x = jax.random.normal(jax.random.PRNGKey(m + k), (m, k)) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(n), (k, n)) * 0.3
    qw = ops.pack_weight_qt(w)
    qx = qtensor.quantize_rows(x, pad_to=2 * qw.payload.shape[0],
                               interpret=True)
    y_two = qtensor.qmm(qx, qw, interpret=True)
    y_fused = qtensor.qmm(x, qw, fuse_act_quant=True, interpret=True)
    assert y_fused.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_two))


def test_gemm_w4a4_fused_explicit_tiles():
    """Direct kernel entry with multi-tile grids in every dimension: the
    prologue re-quantizes the x tile per N tile without perturbing a bit
    vs quantizing once up front."""
    m, k, n = 32, 64, 32
    x = jax.random.normal(jax.random.PRNGKey(44), (m, k), jnp.float32) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(45), (k, n)) * 0.3
    qw = ops.pack_weight_qt(w)
    xp, xs, xs32 = ops.quantize_rows(x, interpret=True)
    for bm, bk, bn in [(8, 16, 16), (16, 32, 32), (32, 64, 16)]:
        y_two = ops.gemm_w4a4(xp, xs, xs32, qw.payload, qw.scales,
                              qw.scale32, bm=bm, bk=bk, bn=bn,
                              interpret=True)
        y_fused = ops.gemm_w4a4_fused(x, xs32, qw.payload, qw.scales,
                                      qw.scale32, bm=bm, bk=bk, bn=bn,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(y_fused),
                                      np.asarray(y_two),
                                      err_msg=f"tiles {(bm, bk, bn)}")


def test_gemm_w4a4_fused_flag_validation():
    """fuse_act_quant must refuse operands it cannot honor rather than
    silently changing dispatch count or numerics: a packed activation
    (already quantized) and a non-kernel weight (would fall back to the
    dense qdq path) both raise."""
    from repro.core import qtensor
    from repro.core.qtensor import BlockLayout1D, QuantSpec, quantize
    x = jax.random.normal(jax.random.PRNGKey(52), (4, 32))
    qw = ops.pack_weight_qt(
        jax.random.normal(jax.random.PRNGKey(53), (32, 16)) * 0.3)
    qx = qtensor.quantize_rows(x, interpret=True)
    with pytest.raises(ValueError, match="already\\s+packed"):
        qtensor.qmm(qx, qw, fuse_act_quant=True, interpret=True)
    qw_1d = quantize(jax.random.normal(jax.random.PRNGKey(54), (32, 16)),
                     QuantSpec("mixfp4", BlockLayout1D(0)))
    with pytest.raises(ValueError, match="kernel-dispatchable"):
        qtensor.qmm(x, qw_1d, fuse_act_quant=True, interpret=True)


def test_dispatch_counter_counts_gemm_path():
    """ops.count_dispatches: the fused path is ONE kernel entry where the
    composition is two (quantize_rows + gemm_w4a4)."""
    from repro.core import qtensor
    x = jax.random.normal(jax.random.PRNGKey(50), (4, 64))
    qw = ops.pack_weight_qt(
        jax.random.normal(jax.random.PRNGKey(51), (64, 32)) * 0.3)
    with ops.count_dispatches() as fused_counts:
        jax.eval_shape(
            lambda a: qtensor.qmm(a, qw, fuse_act_quant=True,
                                  interpret=True), x)
    with ops.count_dispatches() as two_counts:
        jax.eval_shape(
            lambda a: qtensor.qmm(
                qtensor.quantize_rows(a, pad_to=64, interpret=True), qw,
                interpret=True), x)
    assert fused_counts == {"gemm_w4a4_fused": 1}, fused_counts
    assert two_counts == {"quantize_rows": 1, "gemm_w4a4": 1}, two_counts


def test_gemm_w4a16_serving_bytes():
    """Memory win: packed weight is ~3.55x smaller than bf16."""
    k, n = 256, 256
    w = jax.random.normal(jax.random.PRNGKey(6), (k, n))
    qw = ops.pack_weight_qt(w)
    assert k * n * 2 / qw.nbytes > 3.5


def test_quant_kernel_odd_rows():
    """Grid handles M not divisible by the row tile (bm auto-shrink)."""
    x = jax.random.normal(jax.random.PRNGKey(7), (12, 64), jnp.float32)
    p_k, s_k, _ = mixfp4_quant_rows(x, interpret=True, bm=4)
    p_r, s_r, _ = ref.ref_quant_pack_rows(x, "mixfp4")
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


def test_quant_rows_zero_rows_pinned_scale32_canonical_scale_bytes():
    """Regression (type-in-sign safety): all-zero rows — incl. negative
    zeros, and under a pinned ``scale32=`` as the packed KV cache and the
    W4A4 path use — must emit canonical POSITIVE scale bytes.  A
    negative-zero E4M3 scale byte (0x80) has its type bit set, so the
    Fig. 9 decoder would read the dead block as E1M2; the branch guards
    map all-zero blocks to scale 1.0 (byte 0x38), and ``_pack_scale`` now
    structurally forbids a zero-magnitude byte from carrying the type
    bit."""
    for fill in (0.0, -0.0):
        x = jnp.full((2, 64), fill, jnp.float32)
        for kw in ({}, {"scale32": 1.0}, {"scale32": jnp.float32(0.25)}):
            p, s, _ = ops.quantize_rows(x, interpret=True, **kw)
            s_np, p_np = np.asarray(s), np.asarray(p)
            assert (s_np & 0x80 == 0).all(), (fill, kw, s_np)   # E2M1 type
            assert (s_np == 0x38).all(), (fill, kw, s_np)       # scale 1.0
            assert (p_np == 0).all()
            np.testing.assert_array_equal(
                np.asarray(ref.ref_dequant_kv(p, s, 1.0)), 0.0)
    # mixed row: the zero block keeps its canonical byte next to live ones
    x = jnp.zeros((1, 32), jnp.float32).at[0, 16:].set(3.0)
    _, s, _ = ops.quantize_rows(x, interpret=True, scale32=1.0)
    assert int(np.asarray(s)[0, 0]) == 0x38
    # the canonicalization itself: even if a zero-magnitude scale met a
    # set type bit, the packed byte must drop the bit (0x00, never 0x80)
    from repro.core import scaling
    from repro.kernels.mixfp4_quant import _pack_scale
    b = _pack_scale(jnp.zeros((1, 1)), jnp.ones((1, 1), jnp.uint8))
    assert int(np.asarray(b)[0, 0]) == 0x00
    b2 = scaling.pack_scale_with_type(jnp.zeros((1,)),
                                      jnp.ones((1,), jnp.uint8))
    assert int(np.asarray(b2)[0]) == 0x00


@pytest.mark.parametrize("m", [8, 24, 136])
def test_row_tiles_obey_tpu_tiling(m):
    """The quantizer and FWHT row tiles at gemma2-2b's K=2304: a multiple
    of 8 (the (8, 128) tiling rule) or the whole of M — never the odd
    heights a halving search lands on (170 -> 85 -> ... -> 2)."""
    from repro.kernels import fwht, mixfp4_quant
    for pick in (mixfp4_quant._pick_bm, fwht._pick_bm):
        bm = pick(m, 2304)
        assert bm == m or bm % 8 == 0, (pick.__module__, m, bm)
        assert 0 < bm <= m
