"""QTensor API: wire-format equivalence vs the legacy paths, pytree/jit
behaviour, and the qmm dispatcher."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import formats, pack, quantize as Q, qtensor
from repro.core.qtensor import (BlockLayout1D, BlockLayout2D, QTensor,
                                QuantSpec, qmm, quantize)
from repro.kernels import ref
from repro.kernels.mixfp4_gemm import _decode_nibbles


def _rand(shape, seed=0, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


# ---------------------------------------------------------------------------
# wire-format equivalence: new API must be bit-identical to the old
# block_quantize_* -> pack_blocks -> unpack_blocks round trips
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,axis", [((8, 64), -1), ((8, 37), -1),
                                        ((24, 16), 0), ((4, 5, 48), -1)])
@pytest.mark.parametrize("method", ["mixfp4", "nvfp4"])
def test_1d_roundtrip_matches_legacy_path(shape, axis, method):
    x = _rand(shape, seed=sum(shape), scale=2.0)
    qt = quantize(x, QuantSpec(method, BlockLayout1D(axis)))
    bq, n, ax = Q.block_quantize_1d(x, method, axis=axis)
    legacy = Q._from_blocks_1d(pack.unpack_blocks(pack.pack_blocks(bq)), n, ax)
    np.testing.assert_array_equal(np.asarray(qt.dequantize()),
                                  np.asarray(legacy))
    assert qt.shape == tuple(x.shape)


@pytest.mark.parametrize("shape", [(64, 48), (40, 24), (16, 16)])
def test_2d_roundtrip_matches_qdq2d(shape):
    w = _rand(shape, seed=shape[0], scale=0.5)
    qt = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    np.testing.assert_array_equal(np.asarray(qt.dequantize()),
                                  np.asarray(Q.qdq_2d(w, "mixfp4")))


def test_2d_matches_ref_pack_weight_kn():
    w = _rand((64, 48), 3, 0.4)
    qt = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    p, s, s32 = ref.ref_pack_weight_kn(w)
    np.testing.assert_array_equal(np.asarray(qt.payload), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(qt.scales), np.asarray(s))
    np.testing.assert_allclose(float(qt.scale32), float(s32), rtol=0)


def test_kernel_decoder_matches_fig9_reference():
    """The Pallas in-VMEM decoder must match formats.decode_to_e2m2 for all
    16 nibbles x both type bits (the Fig. 9 contract)."""
    nib = jnp.arange(16, dtype=jnp.uint8)
    for t in (0, 1):
        t_full = jnp.full((16,), t, jnp.uint8)
        got = _decode_nibbles(nib, t_full)
        want = formats.decode_to_e2m2(nib, jnp.uint8(t))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wire_bits_and_nbytes():
    x = _rand((64, 128), 2)
    qt = quantize(x, QuantSpec("mixfp4", BlockLayout1D(-1)))
    # 4 bits/value + 8 bits per 16-block (+4B tensor scale)
    assert (qt.nbytes - 4) * 8 == x.size * 4 + (x.size // 16) * 8
    assert qt.bits_per_value == pytest.approx(4.5, abs=0.01)


def test_unpackable_methods_rejected():
    x = _rand((8, 32))
    for m in ["mixfp4_e3", "nvfp4_e3", "four_six", "nvint4"]:
        with pytest.raises(ValueError):
            quantize(x, QuantSpec(m, BlockLayout1D(-1)))


# ---------------------------------------------------------------------------
# pytree behaviour
# ---------------------------------------------------------------------------
def test_pytree_flatten_preserves_metadata():
    qt = quantize(_rand((32, 48)), QuantSpec("mixfp4", BlockLayout2D()))
    leaves, treedef = jax.tree.flatten(qt)
    assert len(leaves) == 3
    qt2 = jax.tree.unflatten(treedef, leaves)
    assert (qt2.method, qt2.layout, qt2.shape, qt2.dtype) == \
        (qt.method, qt.layout, qt.shape, qt.dtype)
    np.testing.assert_array_equal(np.asarray(qt2.payload),
                                  np.asarray(qt.payload))


def test_jit_through_qtensor():
    qt = quantize(_rand((32, 48), 1), QuantSpec("mixfp4", BlockLayout2D()))
    f = jax.jit(lambda q: q.dequantize().sum())
    a = float(f(qt))
    b = float(qt.dequantize().sum())
    assert a == pytest.approx(b, rel=1e-6)


def test_scan_slices_stacked_qtensor():
    """A vmap-quantized per-layer weight stack is one QTensor whose children
    scan slices layer-by-layer (the serving params layout)."""
    wstack = _rand((3, 32, 48), 7, 0.3)
    spec = QuantSpec("mixfp4", BlockLayout2D())
    qts = jax.vmap(lambda m: quantize(m, spec))(wstack)
    x = _rand((4, 32), 8)

    def body(c, qt_layer):
        return c + qmm(x, qt_layer, interpret=True), None

    tot, _ = jax.lax.scan(body, jnp.zeros((4, 48)), qts)
    want = sum(qmm(x, quantize(wstack[i], spec), interpret=True)
               for i in range(3))
    np.testing.assert_allclose(np.asarray(tot), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# qmm dispatch
# ---------------------------------------------------------------------------
def test_qmm_w4a16_matches_dequant_matmul():
    x = _rand((5, 40), 4)           # padded K path (40 -> 48)
    w = _rand((40, 24), 5, 0.3)
    qt = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    y = qmm(x, qt, interpret=True)
    want = jax.lax.dot(x.astype(jnp.bfloat16),
                       qt.dequantize().astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    scale = float(jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(want) / scale, atol=2e-2)
    assert y.shape == (5, 24)


def test_qmm_prime_m_pads_instead_of_degrading():
    """M with no divisor near the tile cap (e.g. prime 131 > 128) must be
    padded to a tile multiple, not served with 1-row grid tiles."""
    x = _rand((131, 32), 18)
    qt = quantize(_rand((32, 16), 19, 0.3), QuantSpec("mixfp4",
                                                      BlockLayout2D()))
    y = qmm(x, qt, interpret=True)
    assert y.shape == (131, 16)
    want = jax.lax.dot(x.astype(jnp.bfloat16),
                       qt.dequantize().astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    scale = float(jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(want) / scale, atol=2e-2)


def test_qmm_nd_activations():
    x = _rand((2, 3, 32), 6)
    qt = quantize(_rand((32, 48), 7, 0.3), QuantSpec("mixfp4",
                                                     BlockLayout2D()))
    y = qmm(x, qt, interpret=True)
    assert y.shape == (2, 3, 48)
    y2 = qmm(x.reshape(6, 32), qt, interpret=True).reshape(2, 3, 48)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


def test_qmm_w4a4_matches_oracle():
    x = _rand((8, 64), 8)
    w = _rand((64, 32), 9, 0.3)
    qx = qtensor.quantize_rows(x, interpret=True)
    qw = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    y = qmm(qx, qw, interpret=True)
    want = ref.ref_gemm_w4a4(qx.payload, qx.scales, qx.scale32,
                             qw.payload, qw.scales, qw.scale32)
    scale = float(jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(want) / scale, atol=2e-2)


def test_stack_matches_vmap_quantize():
    """qtensor.stack of per-layer QTensors == the vmap-quantized stack, and
    mismatched metadata is rejected."""
    wstack = _rand((3, 32, 48), 13, 0.3)
    spec = QuantSpec("mixfp4", BlockLayout2D())
    stacked = qtensor.stack([quantize(wstack[i], spec) for i in range(3)])
    vmapped = jax.vmap(lambda m: quantize(m, spec))(wstack)
    np.testing.assert_array_equal(np.asarray(stacked.payload),
                                  np.asarray(vmapped.payload))
    np.testing.assert_array_equal(np.asarray(stacked.scales),
                                  np.asarray(vmapped.scales))
    assert (stacked.method, stacked.layout, stacked.shape) == \
        (vmapped.method, vmapped.layout, vmapped.shape)

    def body(c, qt_layer):
        return c + qt_layer.dequantize().sum(), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), stacked)
    want = sum(float(quantize(wstack[i], spec).dequantize().sum())
               for i in range(3))
    assert float(tot) == pytest.approx(want, rel=1e-5)

    other = quantize(_rand((16, 16), 14), spec)
    with pytest.raises(ValueError, match="identical QTensor metadata"):
        qtensor.stack([quantize(wstack[0], spec), other])


def test_ops_pack_weight_qt_matches_quantize():
    """The kernels-side producer must stay bit-identical to the real path
    it fronts (the deprecated pack_weight_kn triple shim is REMOVED; only
    pack_weight_qt remains — docs/qtensor.md migration table)."""
    from repro.kernels import ops
    assert not hasattr(ops, "pack_weight_kn")
    w = _rand((32, 48), 17, 0.3)
    a = ops.pack_weight_qt(w)
    b = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    np.testing.assert_array_equal(np.asarray(a.payload), np.asarray(b.payload))
    np.testing.assert_array_equal(np.asarray(a.scales), np.asarray(b.scales))
    assert (a.method, a.layout, a.shape, a.dtype) == \
        (b.method, b.layout, b.shape, b.dtype)


def test_quantize_rows_pad_to_preserves_real_lane_bytes():
    """pad_to zero-pads K onto a wider packed grid (the W4A4 activation
    producer: quantize straight onto a packed weight's Kp grid) without
    perturbing the real lanes' payload/scale bytes — a zero tail never
    moves a block's absmax — and the tail blocks decode to exact zeros."""
    x = _rand((5, 64), 9, 2.0)
    q0 = qtensor.quantize_rows(x, interpret=True)
    q1 = qtensor.quantize_rows(x, pad_to=96, interpret=True)
    assert q1.payload.shape == (5, 48) and q1.scales.shape == (5, 6)
    assert q1.shape == (5, 64)                  # logical shape unchanged
    np.testing.assert_array_equal(np.asarray(q1.payload)[:, :32],
                                  np.asarray(q0.payload))
    np.testing.assert_array_equal(np.asarray(q1.scales)[:, :4],
                                  np.asarray(q0.scales))
    np.testing.assert_allclose(float(q1.scale32), float(q0.scale32), rtol=0)
    np.testing.assert_array_equal(np.asarray(q1.payload)[:, 32:], 0)
    np.testing.assert_array_equal(
        np.asarray(q1.dequantize()), np.asarray(q0.dequantize()))
    with pytest.raises(ValueError, match="pad_to"):
        qtensor.quantize_rows(x, pad_to=40, interpret=True)   # not 16-mult


def test_qmm_w4a4_padded_k_via_pad_to():
    """W4A4 with K not a multiple of 16: quantize_rows(pad_to=Kp) puts the
    activation on the weight's packed grid and qmm contracts only the
    logical lanes (padded lanes decode to exact zeros on both operands)."""
    x = _rand((5, 40), 21)
    w = _rand((40, 24), 22, 0.3)
    qw = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))     # Kp = 48
    qx = qtensor.quantize_rows(x, pad_to=2 * qw.payload.shape[0],
                               interpret=True)
    y = qmm(qx, qw, interpret=True)
    assert y.shape == (5, 24)
    want = ref.ref_gemm_w4a4(qx.payload, qx.scales, qx.scale32,
                             qw.payload, qw.scales, qw.scale32)[:, :24]
    scale = float(jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(want) / scale, atol=2e-2)


def test_qmm_w4a4_logical_k_mismatch_raises():
    """Operands that pad to the same grid but disagree on logical K must
    raise, not silently contract over the padded lanes."""
    qx = qtensor.quantize_rows(_rand((4, 32), 15), interpret=True)  # Kp=32
    qw = quantize(_rand((20, 16), 16, 0.3),                         # Kp=32
                  QuantSpec("mixfp4", BlockLayout2D()))
    with pytest.raises(ValueError, match="K="):
        qmm(qx, qw, interpret=True)


def test_qmm_fallback_for_1d_weight():
    """1-D-blocked weights are not kernel-servable; qmm must fall back to
    the qdq-simulated path rather than fail."""
    x = _rand((4, 32), 10)
    qw = quantize(_rand((32, 16), 11, 0.3), QuantSpec("mixfp4",
                                                      BlockLayout1D(0)))
    y = qmm(x, qw, interpret=True)
    want = jax.lax.dot(x.astype(jnp.bfloat16),
                       qw.dequantize().astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


# ---------------------------------------------------------------------------
# packed checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_packed_tree(tmp_path):
    tree = {
        "layers": {"wq": jax.vmap(
            lambda m: quantize(m, QuantSpec("mixfp4", BlockLayout2D())))(
                _rand((2, 32, 32), 12, 0.3))},
        "ln": jnp.ones((32,)),
    }
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_packed(3, tree)
    restored, extra = mgr.restore_packed()
    qt, qt0 = restored["layers"]["wq"], tree["layers"]["wq"]
    assert isinstance(qt, qtensor.QTensor)
    assert (qt.method, qt.layout, qt.shape) == (qt0.method, qt0.layout,
                                                qt0.shape)
    np.testing.assert_array_equal(np.asarray(qt.payload),
                                  np.asarray(qt0.payload))
    np.testing.assert_array_equal(np.asarray(qt.scales),
                                  np.asarray(qt0.scales))
    np.testing.assert_array_equal(np.asarray(restored["ln"]),
                                  np.asarray(tree["ln"]))
    np.testing.assert_array_equal(
        np.asarray(qt.dequantize()), np.asarray(qt0.dequantize()))


# ---------------------------------------------------------------------------
# prepad_for_tiles: cache padded operands at pack time (PR-6 satellite)
# ---------------------------------------------------------------------------
def test_prepad_for_tiles_reaches_tuner_fixed_point():
    """Off-grid (K, N) storage must be padded until the tuner's
    (k_pad, n_pad) choice equals the storage itself — so qmm stops
    re-padding inside every jitted call — while the logical shape and the
    wire bytes of the logical region are untouched."""
    from repro.kernels import tuning
    w = _rand((40, 24), 21, 0.3)      # off-grid both dims
    qt = quantize(w, QuantSpec("mixfp4", BlockLayout2D()))
    pp = qtensor.prepad_for_tiles(qt, "w4a16", 8)
    assert pp.shape == qt.shape       # logical shape preserved
    kp, np_ = 2 * pp.payload.shape[0], pp.payload.shape[1]
    ch = tuning.select_tiles("w4a16", 8, kp, np_)
    assert (ch.k_pad, ch.n_pad) == (kp, np_)   # fixed point reached
    # original bytes live unchanged in the top-left region; padding is 0
    op, os_ = np.asarray(qt.payload), np.asarray(qt.scales)
    np.testing.assert_array_equal(
        np.asarray(pp.payload)[:op.shape[0], :op.shape[1]], op)
    np.testing.assert_array_equal(
        np.asarray(pp.scales)[:os_.shape[0], :os_.shape[1]], os_)
    assert np.all(np.asarray(pp.payload)[op.shape[0]:] == 0)
    # a second pass is a no-op (the engine re-prepads after load_weights)
    assert qtensor.prepad_for_tiles(pp, "w4a16", 8) is pp


def test_prepad_for_tiles_preserves_qmm_bitwise():
    """qmm over the prepadded weight must be BITWISE what qmm computes
    over the original (it pads to the same tuner grid internally)."""
    x = _rand((8, 40), 22)
    qt = quantize(_rand((40, 24), 23, 0.3),
                  QuantSpec("mixfp4", BlockLayout2D()))
    pp = qtensor.prepad_for_tiles(qt, "w4a16", 8)
    np.testing.assert_array_equal(
        np.asarray(qmm(x, qt, interpret=True)),
        np.asarray(qmm(x, pp, interpret=True)))


def test_prepad_for_tiles_passes_through_non_2d():
    """Stacked (scan) QTensors and 1-D row layouts are not tile-padded:
    they pass through untouched."""
    stacked = qtensor.stack([
        quantize(_rand((32, 16), i, 0.3), QuantSpec("mixfp4",
                                                    BlockLayout2D()))
        for i in range(2)])
    assert qtensor.prepad_for_tiles(stacked, "w4a16", 4) is stacked
    rows = qtensor.quantize_rows(_rand((4, 32), 3), interpret=True)
    assert qtensor.prepad_for_tiles(rows, "w4a4", 4) is rows


def test_pack_projections_stacks_and_passes_packed_leaves_through():
    """A scan-stacked leaf packs to exactly the per-matrix ``quantize``
    bytes (one matrix at a time on the device), and a tree whose
    projections are already packed — another engine's — passes through
    with the same byte accounting; ``act_rht`` refuses it, since the
    rotation applies to dense weights."""
    from repro.models.base import pack_projections
    w = _rand((3, 64, 48), 11, 0.3)
    packed, nbytes, dense = pack_projections({"wq": w})
    spec = QuantSpec("mixfp4", BlockLayout2D())
    for i in range(3):
        qt = quantize(w[i], spec)
        np.testing.assert_array_equal(np.asarray(packed["wq"].payload[i]),
                                      np.asarray(qt.payload))
        np.testing.assert_array_equal(np.asarray(packed["wq"].scales[i]),
                                      np.asarray(qt.scales))
    again, nbytes2, dense2 = pack_projections(packed)
    assert again["wq"] is packed["wq"]
    assert (nbytes2, dense2) == (nbytes, dense)
    with pytest.raises(ValueError, match="already packed"):
        pack_projections(packed, act_rht=True)
