"""The engine's ``serve.*`` spans and the metrics that read them.

A small paged, chunked engine runs on the CPU under the profiler: every
span nests in its step, and the spans' arguments agree with the engine's
own ledger and with the benchmark client's record of each step, which
``mfu`` and both rooflines read.  The readers and the gap naming are
checked on hand-made events, and the existing readers' values on the
recorded chip trace are pinned."""
import gzip
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

from bench import peaks, spec
from bench import spans as sp
from bench import trace as tr
from bench.metrics import (attn_paged_roofline, decode_occupancy,
                           device_idle_share, gemm_w4a16_roofline,
                           kv_pool_occupancy, mfu, prefill_share,
                           step_host_ms)

MS = 1_000_000
DATA = Path(__file__).parent / "data"
NEW_READERS = (step_host_ms, decode_occupancy, kv_pool_occupancy)


# ---------------------------------------------------------------------------
# hand-made events
# ---------------------------------------------------------------------------
def _spans():
    """Two steps: a chunk step (10 ms, 5 of them waiting on the device)
    and a decode step (8 ms, 5 waiting)."""
    return [
        ["bench.step", 0, 11 * MS, {}],
        ["serve.step", 0, 10 * MS, {}],
        ["serve.admit", 0, 1 * MS, {}],
        ["serve.chunk_prefill", 1 * MS, 1 * MS, {"start": 0, "tokens": 8}],
        ["serve.sync.first_token", 2 * MS, 1 * MS, {}],
        ["serve.emit", 3 * MS, 1 * MS, {}],
        ["serve.decode", 4 * MS, 1 * MS,
         {"rows": 4, "held_tokens": 40, "pages": 4}],
        ["serve.sync.tokens", 5 * MS, 4 * MS, {}],
        ["serve.emit", 9 * MS, 1 * MS, {}],
        ["bench.step", 12 * MS, 9 * MS, {}],
        ["serve.step", 12 * MS, 8 * MS, {}],
        ["serve.decode", 12 * MS, 1 * MS,
         {"rows": 2, "held_tokens": 24, "pages": 2}],
        ["serve.sync.tokens", 13 * MS, 5 * MS, {}],
        ["serve.journal", 19 * MS, 1 * MS, {}],
    ]


def _run(spans):
    return {"spans": spans, "engine": {"slots": 4, "page_len": 16}}


def test_readers_on_hand_made_spans():
    run = _run(_spans())
    assert sp.step_host_ms(run["spans"]) == pytest.approx([5.0, 3.0])
    assert step_host_ms.read(run) == pytest.approx(4.0)
    assert decode_occupancy.read(run) == pytest.approx(100 * 6 / 8)
    assert kv_pool_occupancy.read(run) == pytest.approx(100 * 64 / 96)


def test_readers_find_nothing_without_spans():
    for spans in ([], [s for s in _spans() if s[0].startswith("bench.")]):
        for m in NEW_READERS:
            assert m.read(_run(spans)) is None, m.__name__
    # a fixed-slot engine has no pool: its decode spans carry no pages
    no_pool = [s[:3] + [{k: v for k, v in s[3].items() if k != "pages"}]
               for s in _spans()]
    assert kv_pool_occupancy.read(_run(no_pool)) is None
    assert decode_occupancy.read(_run(no_pool)) == pytest.approx(75.0)


@pytest.mark.parametrize("gap,name", [
    ((0.2 * MS, 0.8 * MS), "serve.admit"),        # inside three spans
    ((9.2 * MS, 9.9 * MS), "serve.emit"),
    ((5 * MS, 9 * MS), "serve.sync.tokens"),
    ((2.5 * MS, 3.5 * MS), "serve.step"),         # straddles two phases
    ((9.8 * MS, 11 * MS), "bench.step"),          # mostly after the step
    ((20 * MS, 20.5 * MS), "bench.step"),
    ((30 * MS, 31 * MS), "none"),
])
def test_gap_named_by_innermost_span(gap, name):
    assert sp.name_gap(*gap, _spans()) == name


def test_idle_gaps_by_phase():
    ops = [["fusion.1", 1 * MS, 1 * MS], ["fusion.2", 4 * MS, 1 * MS],
           ["fusion.3", 12 * MS, 1 * MS]]
    t = tr.reduce_events({"device": "d", "modules": [], "ops": ops,
                          "host": [["bench.window", 0, 21 * MS]]})
    spans = sp.clip(_spans(), *t["window"])
    # gaps 13-21, 5-12, 2-4 and 0-1 ms
    assert sp.idle_gaps(t, spans) == [
        ["bench.step", pytest.approx(0.008)],
        ["bench.step", pytest.approx(0.007)],
        ["serve.step", pytest.approx(0.002)],
        ["serve.admit", pytest.approx(0.001)]]
    s = sp.summary(t, spans)
    assert s["steps"] == 2 and s["decode_rows"] == [4, 2]
    assert s["step_host_ms_p50"] == pytest.approx(4.0)
    assert s["phase_s"]["serve.sync.tokens"] == pytest.approx(0.009)


def test_of_run_reads_only_its_own_window(tmp_path):
    run = {"trace": {"window": (0, 10)}}
    assert sp.of_run(run, root=tmp_path) == [] and run["spans"] == []


# ---------------------------------------------------------------------------
# the recorded chip trace (taken before the engine had spans)
# ---------------------------------------------------------------------------
def test_existing_readers_pinned_on_recorded_trace():
    """The values every reader read on this trace before the engine had
    spans; the new readers find nothing in it."""
    raw = json.load(gzip.open(DATA / "phi3_chat_step.trace.json.gz", "rt"))
    t = tr.reduce_events(raw)
    cfg = spec.config("phi3-medium-14b")
    steps = [{"prefill": (512, 1024), "decoded": True, "emitted": 32,
              "decode_ctx": [300 + 7 * i for i in range(31)]},
             {"prefill": (0, 0), "decoded": True, "emitted": 32,
              "decode_ctx": [301 + 7 * i for i in range(32)]}]
    run = {"trace": t, "arch": cfg["arch"], "engine": cfg["engine"],
           "peaks": peaks.peaks("TPU v5 lite"), "steps": steps}
    assert tr.window_s(t) == pytest.approx(0.882408002, rel=1e-12)
    assert tr.busy_s(t) == pytest.approx(0.872270312, rel=1e-12)
    assert [tr.kernel_s(t, k) for k in tr.KERNELS] == pytest.approx(
        [0.35918447, 0.062257641, 0.008385264], rel=1e-12)
    assert device_idle_share.read(run) == pytest.approx(1.1488665081257898,
                                                        rel=1e-12)
    assert prefill_share.read(run) == pytest.approx(64.46763420282653,
                                                    rel=1e-12)
    assert mfu.read(run) == pytest.approx(9.227505578001228, rel=1e-12)
    assert gemm_w4a16_roofline.read(run) == pytest.approx(
        24.66877062622174, rel=1e-12)
    assert attn_paged_roofline.read(run) == pytest.approx(
        3.1043428039469054, rel=1e-12)
    bd = tr.breakdown(t)
    assert bd["idle_gaps"][0] == ["bench.step", pytest.approx(0.003880953)]
    run["spans"] = sp.clip([h + [{}] for h in raw["host"]], *t["window"])
    for m in NEW_READERS:
        assert m.read(run) is None, m.__name__


def test_readers_on_recorded_serve_steps():
    """Two steps of phi3-medium-14b.chat recorded on a TPU v5e with the
    engine's spans: a chunk that leaves its prompt mid-prefill (31 rows
    decode) and the chunk that finishes it (32 rows).  The first step's
    decode dispatch waits for its chunk to run, so its host time is long."""
    raw = json.load(gzip.open(DATA / "phi3_chat_serve_steps.trace.json.gz",
                              "rt"))
    t = tr.reduce_events(raw)
    assert [m[0].split("(")[0] for m in t["modules"]
            if m[2] > 1e8] == ["jit_serve_chunk_prefill", "jit_serve_decode",
                               "jit_serve_chunk_prefill", "jit_serve_decode"]
    assert tr.module_kinds(t).count("decode") == 2
    run = {"trace": t, "engine": spec.config("phi3-medium-14b")["engine"],
           "spans": sp.clip(raw["spans"], *t["window"])}
    assert sp.step_host_ms(run["spans"]) == pytest.approx(
        [566.810895, 5.037282])
    assert step_host_ms.read(run) == pytest.approx(285.9240885)
    assert decode_occupancy.read(run) == pytest.approx(100 * 63 / 64)
    assert kv_pool_occupancy.read(run) == pytest.approx(
        100 * (18671 + 18794) / (2 * 1633 * 16))
    gaps = sp.idle_gaps(t, run["spans"], top=5)
    assert [g[0] for g in gaps] == ["serve.step", "serve.step", "serve.step",
                                    "serve.decode", "serve.step"]
    assert gaps[0][1] == pytest.approx(0.004391544)


# ---------------------------------------------------------------------------
# the engine's spans, on the CPU
# ---------------------------------------------------------------------------
SLOTS, PAGE = 3, 16


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A paged, chunked engine serving four requests to the end under
    the profiler, driven by the benchmark's client."""
    import jax
    from repro.core.qgemm import QuantConfig
    from repro.models.base import ArchConfig, build_model
    from repro.serving.engine import ServeEngine
    from bench import run as bench_run
    cfg = ArchConfig(name="spans-test", family="dense", n_layers=2,
                     d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                     vocab=64, attn_chunk=64,
                     quant=QuantConfig(method="mixfp4"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))[0]
    eng = ServeEngine(cfg, params, batch_size=SLOTS, max_len=64,
                      kv_quant="mixfp4", kv_pool=24, kv_page_len=PAGE,
                      prefill_chunk=8)
    client = bench_run.Client(eng)
    rng = np.random.default_rng(0)
    reqs = [(20, 6), (5, 4), (13, 5), (30, 3)]
    directory = tmp_path_factory.mktemp("trace")
    with tr.capture(str(directory)):
        for uid, (p, n) in enumerate(reqs):
            item = types.SimpleNamespace(
                uid=uid, prompt=rng.integers(1, 64, p).astype(np.int32),
                max_new=n)
            client.submit(item, time.time())
        while eng.has_work():
            client.step()
    events = sp.host_events(sp.newest(directory))
    win = sp.named(events, "bench.window")[0]
    return {"engine": eng, "client": client, "directory": directory,
            "window": (win[1], win[1] + win[2]),
            "spans": sp.clip(events, win[1], win[1] + win[2])}


def test_every_span_nests_in_a_step(served):
    spans = served["spans"]
    by_step = sp.steps(spans)
    inner = [s for s in spans
             if s[0].startswith("serve.") and s[0] != "serve.step"]
    assert sum(len(kids) for _, kids in by_step) == len(inner)
    assert len(by_step) == len(served["client"].steps)
    names = {s[0] for s in inner}
    assert names == {"serve.admit", "serve.chunk_prefill",
                     "serve.sync.first_token", "serve.emit", "serve.decode",
                     "serve.sync.tokens", "serve.journal"}
    for _, kids in by_step:
        k = [c[0] for c in kids]
        assert k.count("serve.decode") <= 1
        assert k.count("serve.chunk_prefill") <= 1
        assert k.count("serve.admit") == 1 and k.count("serve.journal") == 1


def test_span_args_match_the_engine_ledger(served):
    log = list(served["engine"].scheduler.step_log)
    by_step = sp.steps(served["spans"])
    assert len(log) == len(by_step)
    for entry, (_, kids) in zip(log, by_step):
        dec = sp.named(kids, "serve.decode")
        chunk = sp.named(kids, "serve.chunk_prefill")
        assert entry["decode_rows"] == (dec[0][3]["rows"] if dec else 0)
        assert entry["prefill_tokens"] == (chunk[0][3]["tokens"]
                                           if chunk else 0)
        for d in dec:
            a = d[3]
            assert 0 < a["held_tokens"] <= a["pages"] * PAGE
            assert a["rows"] <= SLOTS


def test_client_record_agrees_with_the_spans(served):
    """What ``mfu`` and the rooflines read (``run["steps"]``, rebuilt by
    the client from counters and cursors) is what the engine did."""
    for rec, (_, kids) in zip(served["client"].steps,
                              sp.steps(served["spans"])):
        dec = sp.named(kids, "serve.decode")
        chunk = sp.named(kids, "serve.chunk_prefill")
        assert len(rec["decode_ctx"]) == (dec[0][3]["rows"] if dec else 0)
        if chunk:
            a = chunk[0][3]
            assert rec["prefill"] == (a["start"], a["start"] + a["tokens"])
        else:
            assert rec["prefill"] == (0, 0)
        if dec:
            # cache rows held at the decode: each decoded row's context
            # before its new token, plus a mid-prefill slot's cursor
            held = sum(c - 1 for c in rec["decode_ctx"])
            assert dec[0][3]["held_tokens"] - held in (0, rec["prefill"][1])


def test_readers_on_the_cpu_trace(served):
    run = {"trace": {"window": served["window"]},
           "engine": {"slots": SLOTS, "page_len": PAGE}}
    assert len(sp.of_run(run, root=served["directory"])) == len(
        served["spans"])
    log = list(served["engine"].scheduler.step_log)
    decoded = [e["decode_rows"] for e in log if e["decode_rows"]]
    assert decode_occupancy.read(run) == pytest.approx(
        100 * sum(decoded) / (len(decoded) * SLOTS))
    assert 0 < kv_pool_occupancy.read(run) <= 100
    assert step_host_ms.read(run) > 0
    # a run whose window is not in the newest trace reads nothing
    other = {"trace": {"window": (0, 1)}}
    assert sp.of_run(other, root=served["directory"]) == []
