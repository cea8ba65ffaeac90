"""Chunked-prefill scheduler (serving.scheduler): deterministic tests.

The pure-Python job ledger, the engine validation surface, and the two
serving-level contracts the scheduler exists for — decode fairness (no
in-flight decode is ever delayed by more than the chunk token budget,
counted in per-step token ledgers, never wall-clock) and mid-prefill
cancellation (the job, the slot, and every pool page come back).  The
bitwise chunked==whole-prompt property is in test_scheduler_props.py
(hypothesis); a concrete multi-request stream-equality case rides here
so bare environments still pin it.
"""
import numpy as np
import pytest

import jax

from repro.core.qgemm import QuantConfig
from repro.models.base import ArchConfig, build_model
from repro.serving.engine import Request, RequestState, ServeEngine
from repro.serving.scheduler import ChunkedPrefillScheduler


@pytest.fixture(scope="module")
def small_cfg():
    return ArchConfig(name="sched-test", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=64, attn_chunk=64,
                      quant=QuantConfig(method="mixfp4"))


@pytest.fixture(scope="module")
def params(small_cfg):
    return build_model(small_cfg).init(jax.random.PRNGKey(0))[0]


def _drain(eng, reqs, guard=2000):
    streams = {r.uid: [] for r in reqs}
    n = 0
    while eng.has_work():
        for uid, tok in eng.step():
            streams[uid].append(tok)
        n += 1
        assert n < guard, "engine made no progress"
    return streams


# ---------------------------------------------------------------------------
# pure-Python job ledger
# ---------------------------------------------------------------------------
def test_scheduler_job_lifecycle():
    s = ChunkedPrefillScheduler(4)
    s.enqueue(7, slot=0, req=object(), p_len=10)
    job = s.head()
    assert job.uid == 7 and job.remaining == 10
    assert s.advance(job, 4) is False and job.cursor == 4
    assert s.advance(job, 4) is False and job.remaining == 2
    assert s.advance(job, 2) is True          # job completed and removed
    assert s.head() is None and s.pending_jobs == 0
    rep = s.report()
    assert rep["jobs_completed"] == 1
    assert rep["chunks_run"] == 3
    assert rep["tokens_prefilled"] == 10


def test_scheduler_fifo_drop_restart():
    s = ChunkedPrefillScheduler(8)
    s.enqueue(1, slot=0, req=None, p_len=20)
    s.enqueue(2, slot=1, req=None, p_len=5, start_pos=3)
    assert s.head().uid == 1                  # FIFO: first admitted first
    assert s.get(2).cursor == 3               # suffix job resumes at prefix
    s.advance(s.head(), 8)
    s.drop(1)
    assert s.head().uid == 2
    s.restart(2, start_pos=0)
    assert s.get(2).cursor == 0
    assert s.backlog_tokens() == 5
    s.drop(2)
    assert s.pending_jobs == 0 and s.backlog_tokens() == 0


def test_scheduler_step_ledger():
    s = ChunkedPrefillScheduler(4)
    s.enqueue(1, slot=0, req=None, p_len=6)
    s.note_step(4, 2)
    s.note_step(2, 2)
    s.note_step(0, 2)
    assert [e["prefill_tokens"] for e in s.step_log] == [4, 2, 0]
    assert all(e["decode_rows"] == 2 for e in s.step_log)
    assert s.max_prefill_tokens_per_step() == 4


def test_scheduler_step_ledger_is_bounded():
    """The log keeps the latest LOG_STEPS steps; the largest prefill spend
    is remembered after its step has left the log."""
    s = ChunkedPrefillScheduler(4)
    n = s.LOG_STEPS
    s.note_step(3, 1)
    for k in range(n + 10):
        s.note_step(k % 3, 1)
    assert len(s.step_log) == n
    assert s.step_log[-1]["prefill_tokens"] == (n + 9) % 3
    assert max(e["prefill_tokens"] for e in s.step_log) == 2
    assert s.max_prefill_tokens_per_step() == 3
    assert s.report()["max_prefill_tokens_per_step"] == 3
    assert s.report()["steps_logged"] == n


def test_scheduler_validation():
    with pytest.raises(ValueError, match="chunk budget"):
        ChunkedPrefillScheduler(0)
    s = ChunkedPrefillScheduler(4)
    s.enqueue(1, slot=0, req=None, p_len=4)
    with pytest.raises(ValueError, match="already"):
        s.enqueue(1, slot=1, req=None, p_len=4)


# ---------------------------------------------------------------------------
# engine validation surface
# ---------------------------------------------------------------------------
def test_engine_prefill_chunk_validation(small_cfg, params):
    with pytest.raises(ValueError, match="must be >= 1"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                    prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_buckets"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                    prefill_chunk=4, prefill_buckets="pow2-64")


# ---------------------------------------------------------------------------
# decode fairness: per-step token ledgers, no wall-clock anywhere
# ---------------------------------------------------------------------------
def test_long_admission_never_stalls_decode(small_cfg, params):
    """One near-max-length admission lands while a full decode batch is
    in flight: with the scheduler on, NO step spends more than the chunk
    budget on prefill, and every chunk-spending step still decodes the
    in-flight rows.  The whole-prompt engine provably does stall (its
    worst step spends the full prompt length) — asserted as the control
    so this test keeps meaning if prefill ever gets cheaper."""
    chunk, long_len = 4, 40
    long_prompt = np.arange(long_len, dtype=np.int32) % small_cfg.vocab

    def drive(prefill_chunk):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=64,
                          kv_quant="mixfp4", prefill_chunk=prefill_chunk)
        short = Request(uid=0, prompt=np.array([5, 4, 3], np.int32),
                        max_new_tokens=24)
        eng.add_request(short)
        eng.step()                    # short req decoding (full decode lane)
        long = Request(uid=1, prompt=long_prompt, max_new_tokens=2)
        eng.add_request(long)
        _drain(eng, [short, long])
        assert short.state is RequestState.FINISHED
        assert long.state is RequestState.FINISHED
        return eng

    eng = drive(chunk)
    log = eng.scheduler.step_log
    spending = [e for e in log if e["prefill_tokens"] > 0]
    assert len(spending) >= long_len // chunk
    assert all(e["prefill_tokens"] <= chunk for e in spending)
    assert all(e["decode_rows"] >= 1 for e in spending), \
        "a chunk-spending step starved the in-flight decode"
    assert eng.max_prefill_tokens_per_step <= chunk
    rep = eng.scheduler.report()
    assert rep["jobs_completed"] == 2 and rep["pending_jobs"] == 0

    control = drive(None)
    assert control.max_prefill_tokens_per_step >= long_len


def test_chunked_streams_match_unchunked_under_load(small_cfg, params):
    """Concrete (non-hypothesis) stream oracle: three staggered requests
    through a chunked engine emit bitwise the whole-prompt engine's
    streams — decode junk-row scatters during an in-flight chunked
    prefill land at the job cursor and are overwritten by the next
    chunk, so concurrency cannot perturb the packed cache."""
    prompts = [np.array([9, 8, 7, 3, 1], np.int32),
               (np.arange(30, dtype=np.int32) * 7 + 1) % small_cfg.vocab,
               np.array([1, 2], np.int32)]

    def drive(prefill_chunk):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=48,
                          kv_quant="mixfp4", prefill_chunk=prefill_chunk)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        eng.add_request(reqs[0])
        eng.add_request(reqs[1])      # chunked while req 0 decodes
        eng.step()
        eng.submit(reqs[2])           # queued behind the full batch
        _drain(eng, reqs)
        assert all(r.state is RequestState.FINISHED for r in reqs)
        return {r.uid: list(r.generated) for r in reqs}

    assert drive(4) == drive(None)


# ---------------------------------------------------------------------------
# mid-prefill cancellation releases everything
# ---------------------------------------------------------------------------
def test_cancel_mid_chunked_prefill_releases_slot_and_pages(small_cfg,
                                                            params):
    """cancel(uid) while the admission is still chunking: the job leaves
    the scheduler, the slot frees, every pool page comes back, and the
    prefix tree is untouched (insert() is deferred to prefill completion,
    so a cancelled prompt must never become a reusable prefix)."""
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=64,
                      kv_quant="mixfp4", prefill_chunk=4,
                      kv_pool=9, kv_page_len=16)
    prompt = np.arange(40, dtype=np.int32) % small_cfg.vocab
    req = Request(uid=3, prompt=prompt, max_new_tokens=4)
    eng.add_request(req)
    eng.step()
    eng.step()                                   # two chunks in: mid-prefill
    assert req.state is RequestState.PREFILLING
    assert eng.scheduler.get(3).cursor == 8
    assert eng.pool_report()["pages_active"] > 0

    assert eng.cancel(3) is True
    assert req.state is RequestState.CANCELLED
    assert eng.scheduler.pending_jobs == 0
    assert eng.slots == [None, None]
    pool = eng.pool_report()
    assert pool["pages_active"] == 0
    assert pool["pages_cached"] == 0             # nothing entered the tree
    assert eng.counters["cancelled:user_cancel"] == 1
    assert eng.metrics_report()["counters"]["cancelled:user_cancel"] == 1

    # the pool is fully reusable: a fresh admission of the same prompt is
    # a cold miss (no prefix hit off the cancelled remnant) and finishes
    req2 = Request(uid=4, prompt=prompt, max_new_tokens=2)
    eng.add_request(req2)
    _drain(eng, [req2])
    assert req2.state is RequestState.FINISHED
    assert eng.kv_pool.prefix_hits == 0
    assert eng.pool_report()["pages_active"] == 0
