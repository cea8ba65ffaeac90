"""Operation and byte counts against hand counts."""
import pytest

from bench.metrics import _costs

ARCH = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 100, "mlp_type": "swiglu",
        "window": 0}


def test_w4a16_call():
    ops, nbytes = _costs.w4a16_call(8, 64, 32)
    assert ops == 2 * 8 * 64 * 32
    # 64x32 4-bit codes, (64/16)x(32/16) scale bytes, bf16 in, f32 out
    assert nbytes == 64 * 32 / 2 + 4 * 2 + 8 * 64 * 2 + 8 * 32 * 4


def test_step_counts_every_projection_of_every_layer():
    ops, _ = _costs.w4a16_step(ARCH, 8)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert ops == 2 * 8 * per_layer * 2
    assert _costs.projection_params(ARCH) == per_layer * 2
    gelu = dict(ARCH, mlp_type="gelu")
    assert _costs.projection_params(gelu) == (per_layer - 64 * 128) * 2


def test_attention_decode():
    ops, nbytes = _costs.attn_decode_step(ARCH, [10, 30])
    assert ops == 4 * 40 * 4 * 16 * 2
    # per key: K and V rows of 2 heads, 8 payload + 1 scale bytes each
    assert nbytes == (40 * 2 * 2 * (8 + 1) + 8 * 2 * 4 * 16) * 2


@pytest.mark.parametrize("window,expect", [(0, 50), (20, 20)])
def test_window_bounds_the_context(window, expect):
    assert _costs.attended(dict(ARCH, window=window), 50) == expect


def test_model_flops():
    f = _costs.model_flops(ARCH, tokens=3, contexts=[1, 2, 3], emitted=1)
    assert f == (2 * _costs.projection_params(ARCH) * 3
                 + 4 * 4 * 16 * 2 * 6 + 2 * 64 * 100)
