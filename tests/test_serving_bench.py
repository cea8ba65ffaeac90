"""The serving benchmark's JSON contract (``benchmarks/serving_bench``).

Its own file: the tiny benchmark serves every family on several engine
configurations, minutes of CPU, and the test workers run one file each.
"""


def test_serving_bench_emits_expected_json(tmp_path):
    """The serving benchmark must emit BENCH_serving.json with the schema
    the CI smoke leg (and the perf trajectory) rely on — including the
    W4A16-vs-W4A4 section when --act-quant mixfp4 is passed."""
    import json
    from benchmarks import serving_bench
    out = tmp_path / "BENCH_serving.json"
    results = serving_bench.bench_serving(str(out), tiny=True,
                                          act_quant="mixfp4")
    on_disk = json.loads(out.read_text())
    assert on_disk.keys() == results.keys()
    for key in ("config", "cache_bytes", "decode_step_us", "prefill",
                "act_quant", "kv_pool"):
        assert key in on_disk, key
    assert set(on_disk["decode_step_us"]) == {"bf16", "mixfp4"}
    assert on_disk["cache_bytes"]["ratio"] <= 0.3
    assert on_disk["prefill"]["dispatches_per_admission"] == 1
    aq = on_disk["act_quant"]
    assert set(aq["decode_step_us"]) == {"w4a16", "w4a4", "w4a4_2pass"}
    assert 0.0 <= aq["token_agreement"] <= 1.0
    assert aq["logit_max_abs_delta"] >= 0.0
    # the fused path must match the two-dispatch composition and cost ONE
    # GEMM-path dispatch per projection (the composition costs two)
    assert aq["fused_matches_2pass"] is True
    assert aq["gemm_dispatches_per_projection"]["w4a16"] == 1.0
    assert aq["gemm_dispatches_per_projection"]["w4a4"] == 1.0
    assert aq["gemm_dispatches_per_projection"]["w4a4_2pass"] == 2.0
    # per-row scale32 / serve-time RHT accuracy section (CI smoke leg
    # asserts the full schema; here just the acceptance bits)
    ar = on_disk["act_rowscale"]
    assert set(ar["families"]) == {"dense", "moe", "ssm", "hybrid"}
    assert ar["all_families_not_worse"] is True, ar
    assert all(f["per_row_batch_invariant"] for f in
               ar["families"].values()), ar
    # the paged pool section: paged==fixed streams, real prefix hits
    kp = on_disk["kv_pool"]
    assert kp["paged_matches_fixed"] is True
    assert kp["max_concurrent_requests"] >= 1
    assert kp["prefix_hit_rate"] > 0.0
    assert kp["cache_hit_tokens_per_s"] > 0.0
    assert kp["pool"]["pages_active"] == 0
