"""Drawn packed weights are canonical, have the init's spread, and decode
the same through the reference's own decoder as through the program's."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights as wlib

CFG = json.loads((Path(wlib.__file__).parent / "configs"
                  / "phi3-medium-14b.json").read_text())
ARCH = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 64, "d_ff": 512, "vocab": 300, "mlp_type": "swiglu"}


@pytest.fixture(scope="module")
def drawn():
    return wlib.draw(jax.random.key(3), ARCH, CFG["weights"])


def test_canonical_bytes(drawn):
    for name, (payload, scales, s32) in drawn["proj"].items():
        p, s = np.asarray(payload), np.asarray(scales)
        assert not np.any(s == 0x80), name           # zero-magnitude type bit
        assert not np.any((s & 0x7F) == 0), name     # no dead blocks
        nibs = np.concatenate([p & 0xF, p >> 4])
        assert not np.any(nibs == 0x8), name         # no negative zero


def test_spread_and_type_share(drawn):
    share = CFG["weights"]["e1m2_share"]
    for name, (payload, scales, s32) in drawn["proj"].items():
        p, s = np.asarray(payload[0]), np.asarray(scales[0])
        k = 2 * p.shape[0]
        nib = np.stack([p & 0xF, p >> 4], axis=1).reshape(k, -1)
        t = np.repeat(np.repeat(s >> 7, 16, 0), 16, 1)
        mag = np.where(t == 1, wlib.E1M2_LEVELS[nib & 7],
                       wlib.E2M1_LEVELS[nib & 7])
        sc = np.repeat(np.repeat(wlib.e4m3_value(s), 16, 0), 16, 1)
        w = np.where(nib >> 3, -mag, mag) * sc * float(s32[0])
        assert abs(w.std() * np.sqrt(k) - 1.0) < 0.05, name
        t = np.asarray(scales) >> 7
        assert abs(t.mean() - share) < 0.05, name
    assert drawn["embed"].shape == (wlib.padded_vocab(300), 256)
    assert abs(float(jnp.std(drawn["embed"])) - wlib.EMBED_STD) < 2e-3


def test_reference_decoder_matches_program(drawn):
    from repro.core import qtensor
    payload, scales, s32 = drawn["proj"]["w_up"]
    qt = qtensor.QTensor(payload[1], scales[1], s32[1], "mixfp4",
                         qtensor.BlockLayout2D(16, 16), (256, 512), "float32")
    np.testing.assert_array_equal(
        np.asarray(jax.jit(reference.dequant_2d)(payload[1], scales[1],
                                                 s32[1])),
        np.asarray(qt.dequantize()))


def test_reference_kv_quantizer_matches_program():
    from repro.models import base
    from repro.core import qtensor
    x = jax.random.normal(jax.random.key(5), (16, 2, 128)) * 3.0
    p, s = base.quantize_kv_rows(x)
    prog = qtensor.from_packed_rows(p, s, base.KV_SCALE32).dequantize()
    np.testing.assert_array_equal(np.asarray(jax.jit(reference.mixfp4_rows)(x)),
                                  np.asarray(prog))


def test_same_seed_same_weights(drawn):
    again = wlib.draw(jax.random.key(3), ARCH, CFG["weights"])
    np.testing.assert_array_equal(np.asarray(again["proj"]["wq"][0]),
                                  np.asarray(drawn["proj"]["wq"][0]))


@pytest.mark.parametrize("bits", [0x00, 0x80])
def test_reference_scale_decoder_is_exact(bits):
    """Every E4M3 magnitude, with and without the type bit, decodes to the
    format's value exactly (the decoder builds its powers of two from
    exponent bits, not with ``exp2``)."""
    byte = (np.arange(128) | bits).astype(np.uint8)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(reference.e4m3)(jnp.asarray(byte))),
        wlib.e4m3_value(byte).astype(np.float32))
