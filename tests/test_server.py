"""Async streaming front-end (serving.server): HTTP-level contracts.

Everything runs over a real loopback socket against the real asyncio
server — the worker thread owns the engine, requests stream as SSE
frames, and the chaos sweep extends THROUGH the HTTP layer: an injected
mid-stream fault must surface as exactly one typed error frame on the
poisoned stream while concurrent survivors stay bitwise identical to the
fault-free run (W4A16 decode is row-independent).  Client disconnects
must translate into ``cancel(uid)`` and release the slot and every pool
page.
"""
import json
import threading

import numpy as np
import pytest

import jax

from repro.core.qgemm import QuantConfig
from repro.models.base import ArchConfig, build_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.faults import FaultInjector, FaultRule
from repro.serving.metrics import render_prometheus
from repro.serving.server import (ServingServer, get_json, resume_stream,
                                  scrape_metrics, stream_generate)


@pytest.fixture(scope="module")
def small_cfg():
    return ArchConfig(name="server-test", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=64, attn_chunk=64,
                      quant=QuantConfig(method="mixfp4"))


@pytest.fixture(scope="module")
def params(small_cfg):
    return build_model(small_cfg).init(jax.random.PRNGKey(0))[0]


def _engine(small_cfg, params, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_len", 32)
    return ServeEngine(small_cfg, params, **kw)


def _tokens(frames):
    return [f["token"] for f in frames if f["type"] == "token"]


def _serve_direct(eng, prompt, n_new):
    """Oracle: drive an engine without the HTTP layer."""
    req = Request(uid=0, prompt=np.asarray(prompt, np.int32),
                  max_new_tokens=n_new)
    eng.add_request(req)
    toks = []
    while eng.has_work():
        toks.extend(t for _, t in eng.step())
    return toks


def test_stream_matches_direct_drive(small_cfg, params):
    """One request over HTTP: token frames in order, ONE terminal frame
    with the typed finish reason, and the stream is bitwise the direct
    engine drive's."""
    prompt, n_new = [1, 2, 3, 4, 5, 6, 7, 8], 6
    with ServingServer(_engine(small_cfg, params,
                               prefill_chunk=4)) as srv:
        frames = list(stream_generate(srv.host, srv.port, prompt,
                                      max_new_tokens=n_new))
    terminal = [f for f in frames if f["type"] in ("done", "error")]
    assert len(terminal) == 1 and frames[-1] is terminal[0]
    assert terminal[0]["type"] == "done"
    assert terminal[0]["finish_reason"] == "max_new_tokens"
    assert terminal[0]["state"] == "FINISHED"
    assert terminal[0]["n_tokens"] == n_new
    assert [f["index"] for f in frames[:-1]] == list(range(n_new))
    toks = _tokens(frames)
    assert toks == _serve_direct(_engine(small_cfg, params), prompt, n_new)


def test_concurrent_streams_and_metrics_scrape(small_cfg, params):
    """Two concurrent HTTP streams share the decode batch; /metrics
    renders the registry (TTFT/ITL summaries, gauges) mid-flight."""
    prompts = {10: [5, 4, 3], 11: [9, 8, 7, 6]}
    got: dict = {}

    def client(uid):
        got[uid] = list(stream_generate(srv.host, srv.port, prompts[uid],
                                        max_new_tokens=8, uid=uid))

    with ServingServer(_engine(small_cfg, params)) as srv:
        threads = [threading.Thread(target=client, args=(u,))
                   for u in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        text = scrape_metrics(srv.host, srv.port)
    for uid in prompts:
        assert got[uid][-1]["type"] == "done", got[uid][-1]
        assert len(_tokens(got[uid])) == 8
    assert "mixfp4_ttft_ms_count 2" in text
    assert "mixfp4_itl_ms" in text
    assert "mixfp4_queue_depth" in text
    assert 'mixfp4_ttft_ms{quantile="0.99"}' in text
    # W4A16 row independence: each stream is bitwise its solo drive
    for uid in prompts:
        solo = _serve_direct(_engine(small_cfg, params), prompts[uid], 8)
        assert _tokens(got[uid]) == solo, uid


def test_chaos_through_http_one_error_frame_survivors_bitwise(small_cfg,
                                                              params):
    """Chaos THROUGH the HTTP layer: a decode-site nan pinned to one uid
    fails exactly that stream with ONE typed error frame; the concurrent
    survivor's stream is bitwise the fault-free run (W4A16)."""
    victim, survivor = 40, 41
    prompts = {victim: [3, 1, 4, 1, 5], survivor: [2, 7, 1, 8]}
    inj = FaultInjector(0, [FaultRule("decode", "nan", prob=1.0,
                                      uid=victim)])
    got: dict = {}

    def client(uid):
        got[uid] = list(stream_generate(srv.host, srv.port, prompts[uid],
                                        max_new_tokens=6, uid=uid))

    with ServingServer(_engine(small_cfg, params, faults=inj)) as srv:
        threads = [threading.Thread(target=client, args=(u,))
                   for u in (victim, survivor)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)

    verr = [f for f in got[victim] if f["type"] == "error"]
    assert len(verr) == 1 and got[victim][-1] is verr[0]
    assert verr[0]["finish_reason"] == "nan_logits"
    assert verr[0]["state"] == "FAILED"
    assert not any(f["type"] == "error" for f in got[survivor])
    assert got[survivor][-1]["finish_reason"] == "max_new_tokens"
    fault_free = _serve_direct(_engine(small_cfg, params),
                               prompts[survivor], 6)
    assert _tokens(got[survivor]) == fault_free


def test_disconnect_mid_stream_cancels_and_releases(small_cfg, params):
    """Satellite regression: the client hangs up after the first token;
    the server must turn the EOF into ``cancel(uid)`` — slot freed, every
    pool page released, and the registry counts the ``user_cancel``
    finish exactly once."""
    eng = _engine(small_cfg, params, max_len=64, kv_quant="mixfp4",
                  prefill_chunk=4, kv_pool=9, kv_page_len=16)
    prompt = list(range(1, 24))
    with ServingServer(eng) as srv:
        frames = list(stream_generate(srv.host, srv.port, prompt,
                                      max_new_tokens=30, abort_after=1))
        # the abort closes the socket with the request still decoding —
        # wait (bounded) for the worker to observe the EOF and cancel
        deadline = 200
        while eng.counters.get("cancelled:user_cancel", 0) == 0:
            deadline -= 1
            assert deadline > 0, "disconnect never became cancel(uid)"
            threading.Event().wait(0.05)
    assert all(f["type"] == "token" for f in frames)   # hung up pre-terminal
    assert eng.counters["cancelled:user_cancel"] == 1
    assert eng.slots == [None, None]
    pool = eng.pool_report()
    assert pool["pages_active"] == 0
    rep = eng.metrics_report()
    assert rep["counters"]["cancelled:user_cancel"] == 1
    assert rep["gauges"]["active_slots"] == 0.0


def test_validation_error_is_a_typed_frame(small_cfg, params):
    """An invalid request (empty prompt) must come back as ONE typed
    error frame over the stream — not a hung connection."""
    with ServingServer(_engine(small_cfg, params)) as srv:
        frames = list(stream_generate(srv.host, srv.port, [],
                                      max_new_tokens=4))
    assert len(frames) == 1
    assert frames[0]["type"] == "error"
    assert frames[0]["finish_reason"] == "empty_prompt"
    assert frames[0]["state"] == "REJECTED"


def test_healthz_and_404(small_cfg, params):
    import http.client
    with ServingServer(_engine(small_cfg, params)) as srv:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        assert r.status == 200 and json.loads(r.read())["ok"] is True
        conn2 = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        conn2.request("GET", "/nope")
        assert conn2.getresponse().status == 404


# ---------------------------------------------------------------------------
# PR 10: bounded sinks, readiness phases, stream resume
# ---------------------------------------------------------------------------
def _parse_sse_blob(blob: bytes):
    """Decode every SSE frame out of a raw captured byte stream (HTTP
    header and chunk-size lines carry no ``data:`` prefix, so they fall
    out naturally)."""
    frames = []
    for raw in blob.split(b"\n\n"):
        i = raw.find(b"data: ")
        if i >= 0:
            frames.append(json.loads(raw[i + len(b"data: "):]))
    return frames


def test_slow_client_hits_bounded_sink_and_is_cancelled(small_cfg, params):
    """A client that stops reading must not wedge the engine or grow the
    sink queue without bound: past ``max_sink_frames`` the request is
    cancelled with the typed ``slow_client`` reason, exactly ONE error
    terminal goes on the wire, and the slot (and its tokens/frames
    backlog) is released while the engine keeps stepping."""
    import socket
    import time

    eng = _engine(small_cfg, params, max_len=128)
    # tiny kernel buffers on BOTH ends so ~100 frames overflow them, and
    # a tiny sink bound so the overflow trips fast
    with ServingServer(eng, max_sink_frames=8, sndbuf=512) as srv:
        body = json.dumps({"prompt": [1, 2, 3, 4], "uid": 77,
                           "max_new_tokens": 120}).encode()
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            # RCVBUF must shrink BEFORE connect: the TCP window is
            # negotiated at the handshake
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 512)
            sock.settimeout(120.0)
            sock.connect((srv.host, srv.port))
            sock.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                         b"Content-Type: application/json\r\n"
                         b"Content-Length: " + str(len(body)).encode()
                         + b"\r\n\r\n" + body)
            # ...and then never read: the engine must cancel us, not hang
            deadline = time.time() + 120.0
            while time.time() < deadline:
                counters = srv.worker.call(lambda e: dict(e.counters),
                                           timeout=30.0)
                if counters.get("cancelled:slow_client"):
                    break
                time.sleep(0.05)
            assert counters.get("cancelled:slow_client") == 1, counters
            # the stalled request's slot is free again
            active = srv.worker.call(
                lambda e: sum(s is not None for s in e.slots),
                timeout=30.0)
            assert active == 0
            # NOW read what the server managed to send: buffered token
            # frames, then exactly one typed error terminal
            blob = b""
            sock.settimeout(10.0)
            while True:
                try:
                    data = sock.recv(65536)
                except TimeoutError:
                    break
                if not data:
                    break
                blob += data
        finally:
            sock.close()
    frames = _parse_sse_blob(blob)
    terminal = [f for f in frames if f["type"] in ("done", "error")]
    assert len(terminal) == 1 and frames[-1] is terminal[0], frames[-2:]
    assert terminal[0]["type"] == "error"
    assert terminal[0]["state"] == "CANCELLED"
    assert terminal[0]["finish_reason"] == "slow_client"
    assert len(_tokens(frames)) < 120


def test_readyz_phases_and_gauges(small_cfg, params):
    """/healthz is pure liveness (200 in every phase); /readyz flips
    ready -> draining and carries the queue/slot/pool gauges."""
    with ServingServer(_engine(small_cfg, params)) as srv:
        assert srv.worker.ready.wait(60.0)
        code, body = get_json(srv.host, srv.port, "/readyz")
        assert code == 200 and body["ready"] is True
        assert body["phase"] == "ready"
        assert body["queue_depth"] == 0 and body["active_slots"] == 0
        assert body["batch_size"] == 2 and body["pool"] is None
        srv.worker.call(lambda e: e.begin_drain())
        code, body = get_json(srv.host, srv.port, "/readyz")
        assert code == 503 and body["ready"] is False
        assert body["phase"] == "draining"
        code, body = get_json(srv.host, srv.port, "/healthz")
        assert code == 200 and body["ok"] is True      # still alive
        assert body["phase"] == "draining"
        rep = srv.drain()
        assert rep["drained"] and rep["survivors"] == []


def test_readyz_reports_pool_gauges(small_cfg, params):
    eng = _engine(small_cfg, params, kv_quant="mixfp4", kv_pool=9,
                  kv_page_len=16)
    with ServingServer(eng) as srv:
        code, body = get_json(srv.host, srv.port, "/readyz")
    assert code == 200
    assert body["pool"]["pages_total"] > 0
    assert body["pool"]["pages_free"] == body["pool"]["pages_total"]
    assert body["pool"]["pages_active"] == 0


def test_metrics_text_has_one_family_per_name(small_cfg, params):
    """A paged, chunked engine mid-flight: every metric family is declared
    once (a repeated ``# TYPE`` line is invalid exposition), and the
    queue and pool gauges are still there, once each."""
    eng = _engine(small_cfg, params, kv_quant="mixfp4", kv_pool=9,
                  kv_page_len=16, prefill_chunk=4)
    for uid, prompt in enumerate([[5, 4, 3, 2, 1, 7], [9, 8, 7]]):
        eng.submit(Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                           max_new_tokens=6))
    for _ in range(4):
        eng.step()
    text = render_prometheus(eng.metrics_report())
    types = [ln.split()[2] for ln in text.splitlines()
             if ln.startswith("# TYPE ")]
    assert len(types) == len(set(types)), sorted(
        t for t in set(types) if types.count(t) > 1)
    for family in ("mixfp4_queue_depth", "mixfp4_kv_pool_pages_active"):
        assert types.count(family) == 1
        assert len([ln for ln in text.splitlines()
                    if ln.startswith(family + " ")]) == 1
    assert "mixfp4_kv_pool_pages_active 2" in text


def test_resume_replays_finished_stream_bitwise(small_cfg, params):
    """GET /resume/{uid} after the stream finished: every token comes
    back flagged ``replayed`` with its original index, then the original
    terminal — the reconnect path a crashed client (or a recovered
    server's clients) uses."""
    prompt, n_new = [1, 2, 3, 4], 6
    with ServingServer(_engine(small_cfg, params)) as srv:
        live = list(stream_generate(srv.host, srv.port, prompt, uid=21,
                                    max_new_tokens=n_new))
        again = list(resume_stream(srv.host, srv.port, 21))
        missing = list(resume_stream(srv.host, srv.port, 999))
    assert _tokens(again) == _tokens(live)
    tok_frames = [f for f in again if f["type"] == "token"]
    assert all(f.get("replayed") for f in tok_frames)
    assert [f["index"] for f in tok_frames] == list(range(n_new))
    assert again[-1]["type"] == "done"
    assert again[-1]["finish_reason"] == "max_new_tokens"
    assert len(missing) == 1 and missing[0]["type"] == "http_error"
    assert "404" in missing[0]["status"]
