"""The trace reduction on a small hand-made event list."""
import pytest

from bench import trace as tr
from bench.metrics import (device_idle_share, gemm_w4a16_roofline,
                           prefill_share)

MS = 1_000_000


def _events():
    return {
        "device": "/device:TPU:0",
        "host": [["bench.window", 0, 100 * MS], ["bench.step", 0, 60 * MS],
                 ["bench.tokens", 60 * MS, 10 * MS],
                 ["bench.wait", 70 * MS, 30 * MS]],
        # a prefill program (0-30 ms), a decode program (40-60 ms), an
        # eager op (65-66 ms) and one op after the window closes
        "modules": [["jit__lambda", 0, 30 * MS], ["jit__lambda", 40 * MS, 20 * MS],
                    ["jit_argmax", 65 * MS, 1 * MS]],
        "ops": [["while.9", 0, 30 * MS],     # a loop spans its body
                ["mixfp4_gemm_w4a16.1", 0, 20 * MS],
                ["fusion.2", 20 * MS, 10 * MS],
                ["mixfp4_gemm_w4a16.3", 40 * MS, 5 * MS],
                ["mixfp4_attn_decode_paged.4", 45 * MS, 10 * MS],
                ["fusion.5", 55 * MS, 5 * MS],
                ["reduce.6", 65 * MS, 1 * MS],
                ["fusion.7", 120 * MS, 5 * MS]],
    }


def test_busy_idle_and_kernels():
    t = tr.reduce_events(_events())
    assert tr.window_s(t) == pytest.approx(0.1)
    assert tr.busy_s(t) == pytest.approx(0.051)
    assert tr.kernel_s(t, "mixfp4_gemm_w4a16") == pytest.approx(0.025)
    assert tr.kernel_s(t, "mixfp4_attn_decode_paged") == pytest.approx(0.010)
    assert tr.module_kinds(t) == ["prefill", "decode", "other"]
    assert tr.op_time_by_kind(t) == pytest.approx(
        {"prefill": 0.030, "decode": 0.020, "other": 0.001})


def test_readers():
    t = tr.reduce_events(_events())
    run = {"trace": t}
    assert device_idle_share.read(run) == pytest.approx(49.0)
    assert prefill_share.read(run) == pytest.approx(100 * 30 / 51)


def test_breakdown_names_gaps_by_host_span():
    bd = tr.breakdown(tr.reduce_events(_events()))
    assert bd["device_ops"][0] == ["mixfp4_gemm_w4a16", pytest.approx(0.025)]
    assert ["fusion", pytest.approx(0.015)] in bd["device_ops"]
    # gaps: 30-40 (step), 60-65 (tokens), 66-100 (wait)
    assert bd["idle_gaps"] == [["bench.wait", pytest.approx(0.034)],
                               ["bench.step", pytest.approx(0.010)],
                               ["bench.tokens", pytest.approx(0.005)]]


def test_readers_find_nothing_in_an_empty_trace():
    t = tr.reduce_events({"device": "d", "host": [["bench.window", 0, MS]],
                          "modules": [], "ops": []})
    run = {"trace": t, "steps": [], "arch": {}, "engine": {}, "peaks": {}}
    assert device_idle_share.read(run) is None
    assert prefill_share.read(run) is None
    assert gemm_w4a16_roofline.read(run) is None


def test_recorded_chip_trace():
    """One chunk-prefill step and one decode step of phi3-medium-14b.chat,
    recorded on a TPU v5e (ops kept by name, start and duration)."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).parent / "data" / "phi3_chat_step.trace.json.gz"
    raw = json.load(gzip.open(path, "rt"))
    t = tr.reduce_events(raw)
    kinds = tr.module_kinds(t)
    assert kinds.count("prefill") == 1 and kinds.count("decode") == 1
    by = tr.op_time_by_kind(t)
    big = {m[0]: m[2] / 1e9 for m in raw["modules"] if m[2] > 1e8}
    assert sorted(big.values()) == pytest.approx(
        sorted([by["decode"], by["prefill"]]), rel=0.02)
    gemm = sum(o[2] for o in raw["ops"]
               if o[0].startswith("mixfp4_gemm_w4a16")) / 1e9
    assert tr.kernel_s(t, "mixfp4_gemm_w4a16") == pytest.approx(gemm)
    assert 0 < tr.busy_s(t) <= tr.window_s(t)
    run = {"trace": t}
    assert prefill_share.read(run) == pytest.approx(
        100 * by["prefill"] / sum(by.values()))
    assert 0 < device_idle_share.read(run) < 5
    bd = tr.breakdown(t)
    assert bd["device_ops"][0][0] == "mixfp4_gemm_w4a16"
    assert len(bd["idle_gaps"]) == 10
