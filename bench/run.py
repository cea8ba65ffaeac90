"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It draws the cell's packed weights on the device from the
seed, builds the program's ``ServeEngine`` (paged MixFP4 KV pool, chunked
prefill, W4A16 projections), warms up every shape the cell's traffic uses,
runs the traffic's pre-roll, then drives ``submit``/``step`` for
``--seconds`` on the wall clock from a host-side load generator, stamping
every token on the client side.  After the window it reads the peak device
memory, copies what the engine wrote to its pool for a sample of the
requests in flight, frees the engine, and compares that and the served
tokens with the plain reference (``bench/reference.py``, ``check``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``check``, each compared number with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits with
status 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"
WARMUP_UID0 = 10**7


def _paths():
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


_paths()

import numpy as np  # noqa: E402

from bench import loadgen, spec  # noqa: E402


class NoChip(RuntimeError):
    pass


def _environment():
    """Before JAX starts: the compile cache at a fixed path in the
    checkout, and no TPU runtime logs outside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def _device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[0], len(devs)


def _arch_config(arch: dict):
    from repro.models.base import ArchConfig
    return ArchConfig(
        name=arch["name"], family="dense", n_layers=arch["n_layers"],
        d_model=arch["d_model"], n_heads=arch["n_heads"],
        n_kv_heads=arch["n_kv_heads"], head_dim=arch["head_dim"],
        d_ff=arch["d_ff"], vocab=arch["vocab"], mlp_type=arch["mlp_type"],
        rope_theta=arch["rope_theta"], norm_eps=arch["norm_eps"],
        window=arch["window"], attn_chunk=arch["attn_chunk"])


def _program_params(w: dict, arch: dict):
    """The drawn arrays as the program's parameter tree, projections as
    packed ``QTensor`` leaves (no copy: the same device buffers)."""
    from repro.core import qtensor
    from bench.weights import projections
    shapes = projections(arch)
    lay = qtensor.BlockLayout2D(16, 16)

    def qt(name):
        p, s, s32 = w["proj"][name]
        return qtensor.QTensor(p, s, s32, "mixfp4", lay, shapes[name],
                               "float32")

    attn = {n: qt(n) for n in ("wq", "wk", "wv", "wo")}
    mlp = {n: qt(n) for n in ("w_up", "w_gate", "w_down") if n in shapes}
    return {"embed": w["embed"],
            "layers": {"ln_attn": w["ln_attn"], "attn": attn,
                       "ln_mlp": w["ln_mlp"], "mlp": mlp},
            "ln_f": w["ln_f"]}


class _Compiles:
    """Counts compilations and cache loads (JAX monitoring events)."""

    def __init__(self):
        import jax
        self.n = 0
        self.window = (float("inf"), float("inf"))

        def listen(event, duration, **kw):
            if (self.window[0] <= time.time() < self.window[1]
                    and ("compile" in event or "cache_retrieval" in event
                         or "trace_duration" in event)):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------
class Client:
    """Submits requests to the engine and stamps what comes back."""

    def __init__(self, engine):
        self.engine = engine
        self.rec: dict[int, dict] = {}
        self.steps: list[dict] = []
        self.lateness: list[float] = []
        self.tracing = False

    def submit(self, item, due_at: float):
        from repro.serving.engine import Request
        from bench import trace as tr
        now = time.time()
        r = {"item": item, "due": due_at, "sent": now, "tokens": [],
             "times": [], "req": None}
        self.rec[item.uid] = r
        self.lateness.append(now - due_at)
        req = Request(uid=item.uid, prompt=item.prompt,
                      max_new_tokens=item.max_new)
        r["req"] = req
        with tr.span("submit"):
            try:
                self.engine.submit(req)
            except (ValueError, RuntimeError) as e:   # refused: never served
                r["error"] = repr(e)

    def step(self):
        from bench import trace as tr
        eng = self.engine
        before = eng.counters.get("prefill_tokens", 0)
        t0 = time.time()
        with tr.span("step"):
            out = eng.step()
        t1 = time.time()
        with tr.span("tokens"):
            n_pre = eng.counters.get("prefill_tokens", 0) - before
            prefill = (0, 0)
            decode_ctx = []
            first = None
            for uid, tok in out:
                r = self.rec.get(uid)
                if r is None:
                    continue
                k = len(r["tokens"])
                r["tokens"].append(int(tok))
                r["times"].append(t1)
                if k == 0:
                    first = r
                else:
                    decode_ctx.append(len(r["item"].prompt) + k)
            if n_pre:
                if first is not None:
                    p = len(first["item"].prompt)
                    prefill = (p - n_pre, p)
                else:
                    job = eng.scheduler.head() if eng.scheduler else None
                    if job is not None:
                        prefill = (job.cursor - n_pre, job.cursor)
            self.steps.append({"t0": t0, "t1": t1, "prefill": prefill,
                               "decode_ctx": decode_ctx,
                               "decoded": bool(decode_ctx),
                               "emitted": len(out), "traced": self.tracing})
        return out


def _warmup(client, items):
    """Serve the warm-up requests to the end: compiles and runs the chunked
    prefill, the decode step and every eager helper the window uses."""
    for it in items:
        client.submit(it, time.time())
    while client.engine.has_work():
        client.step()
    bad = [u for u in (it.uid for it in items)
           if str(client.rec[u]["req"].state) != "FINISHED"]
    if bad:
        raise RuntimeError(f"warm-up requests {bad} did not finish")


class _Tracer:
    """Starts the profiler at ``at[0]`` and stops it at ``at[1]``
    (absolute times), between engine steps."""

    def __init__(self, client, at, directory):
        self.client, self.at, self.dir, self.ctx = client, at, directory, None

    def tick(self, now):
        from bench import trace as tr
        if not self.at:
            return
        if self.ctx is None and self.at[0] <= now < self.at[1]:
            self.ctx = tr.capture(self.dir)
            self.ctx.__enter__()
            self.client.tracing = True
        elif self.client.tracing and now >= self.at[1]:
            self.close()

    def close(self):
        if self.client.tracing:
            self.client.tracing = False
            self.ctx.__exit__(None, None, None)


def _window(client, items, i, t_begin, t_end, tracer) -> int:
    """Open loop: from ``items[i]`` on, each request is sent when due
    (``t_begin + due``), between engine steps, until ``t_end``.  Returns
    the index of the first request not sent."""
    from bench import trace as tr
    n = len(items)
    while True:
        now = time.time()
        if now >= t_end:
            break
        tracer.tick(now)
        while i < n and t_begin + items[i].due <= now:
            client.submit(items[i], t_begin + items[i].due)
            i += 1
        if client.engine.has_work():
            client.step()
        else:
            nxt = t_begin + items[i].due if i < n else t_end
            if client.tracing:
                nxt = min(nxt, tracer.at[1])
            with tr.span("wait"):
                time.sleep(max(0.0, min(nxt, t_end) - time.time()))
    tracer.close()
    return i


def _load(engine) -> dict:
    """Requests holding a slot, and requests waiting for one."""
    return {"live": sum(s is not None for s in engine.slots),
            "queued": len(engine.queue)}


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def end_to_end(client, due_uids, t0, seconds, setup_s):
    end = t0 + seconds
    failed = 0
    for u in due_uids:
        r = client.rec[u]
        req = r["req"]
        if "error" in r or (req.state.terminal
                             and str(req.state) != "FINISHED"):
            failed += 1         # refused or failed; a late answer is not
    gaps, emitted = [], 0
    for r in client.rec.values():
        ts = r["times"]
        emitted += sum(1 for t in ts if t0 <= t <= end)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])
                 if t0 <= a and b <= end]
    m = {"output_tok_per_s": emitted / seconds,
         "itl_p50_ms": _percentile(gaps, 50),
         "itl_p95_ms": _percentile(gaps, 95),
         "setup_s": setup_s}
    diag = {"due": len(due_uids), "tokens_in_window": emitted,
            "itl_samples": len(gaps),
            "late_p50_ms": _percentile(np.array(client.lateness) * 1e3, 50),
            "late_max_ms": (max(client.lateness) * 1e3
                            if client.lateness else None)}
    return m, failed, diag


def _step_times(steps, a, b) -> dict:
    """Median host time of the window's steps, by what they carried."""
    dec = [s["t1"] - s["t0"] for s in steps
           if a <= s["t0"] and s["t1"] <= b and s["prefill"][1] == 0
           and s["decoded"]]
    pre = [s["t1"] - s["t0"] for s in steps
           if a <= s["t0"] and s["t1"] <= b and s["prefill"][1] > 0]
    return {"step_ms_decode_p50": _percentile(np.array(dec) * 1e3, 50),
            "step_ms_prefill_p50": _percentile(np.array(pre) * 1e3, 50),
            "steps_decode": len(dec), "steps_prefill": len(pre)}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def sample(engine, client, packed: int, seed_seq) -> list:
    """Requests in flight at the end of the run whose history fits one
    packed sequence of ``packed`` tokens: the one with the longest served
    stream, then others in an order drawn from the seed.  Each entry is
    (slot, record, positions the pool holds = prompt + served - 1)."""
    live = []
    for i, req in enumerate(engine.slots):
        r = client.rec.get(req.uid) if req is not None else None
        if r is None or str(req.state) != "RUNNING" or not r["tokens"]:
            continue
        n_pos = int(engine.lengths[i])
        if n_pos != len(r["item"].prompt) + len(r["tokens"]) - 1:
            raise RuntimeError(f"request {req.uid}: the pool holds {n_pos} "
                               f"positions, the client saw "
                               f"{len(r['item'].prompt)} + "
                               f"{len(r['tokens'])} - 1")
        live.append((i, r, n_pos))
    if not live:
        return []
    live.sort(key=lambda x: -len(x[1]["tokens"]))
    rng = np.random.default_rng(seed_seq)
    order = [live[0]] + [live[1:][j] for j in rng.permutation(len(live) - 1)]
    out, at = [], 0
    for x in order:
        if at + x[2] <= packed:
            out.append(x)
            at += x[2]
    return out


def cache_rows(engine, picked: list, packed: int):
    """What the timed path wrote to its paged pool for the ``picked``
    requests, every layer: K and V rows as packed bytes laid out as one
    packed sequence (L, packed, Hkv, dh/2) and (L, packed, Hkv, dh/16),
    and the per-layer scale (L,).  Gathered on the device through the
    engine's block tables; padding rows read the pool's first row."""
    import jax.numpy as jnp
    page = engine.kv_page_len
    idx = np.zeros(packed, np.int32)
    at = 0
    for slot, _, n_pos in picked:
        pages = np.asarray(engine.block_tables[slot][:-(-n_pos // page)])
        rows = (pages[:, None] * page + np.arange(page)).reshape(-1)
        idx[at:at + n_pos] = rows[:n_pos]
        at += n_pos
    idx = jnp.asarray(idx)

    def gather(a):
        flat = a.reshape(a.shape[0], -1, *a.shape[3:])
        return jnp.take(flat, idx, axis=1)

    return [(gather(qt.payload), gather(qt.scales), qt.scale32)
            for qt in (engine.cache["k"], engine.cache["v"])]


def _kv_rel_err(st) -> list:
    """Per layer, the larger of K's and V's relative L2 distance."""
    st = np.asarray(st, np.float64)
    return np.maximum(np.sqrt(st[:, 0] / np.maximum(st[:, 1], 1e-30)),
                      np.sqrt(st[:, 2] / np.maximum(st[:, 3], 1e-30))
                      ).tolist()


def check(weights, arch, picked, kv, packed, control=False) -> dict:
    """The timed path's output against the reference, for the ``picked``
    requests (``sample``) laid out as one packed sequence of their prompts
    and served tokens; ``kv`` is what the engine wrote to its pool for
    them (``cache_rows``).

    The reference runs every layer teacher-forced on the pool: layer
    ``l`` attends over the program's own K/V rows of layer ``l``, so each
    layer starts from the history the program had, and the 4-bit rounding
    of an earlier layer's rows cannot compound through the depth.  Two
    numbers:

    * ``kv_differ_share``: over every layer, the largest share of K (or
      V) elements where the rows the program wrote differ from the
      reference's own MixFP4 rows of that layer (``kv_rel_err``, their
      relative L2 distance, beside it).  A share counts the codes that a
      perturbation pushes across a rounding boundary, so it grows in
      proportion to the perturbation, where the L2 distance of 4-bit
      rows grows with its square root.  It covers the embedding, every
      norm, projection GEMM, RoPE and KV quantizer, and the attention
      (chunked prefill and paged decode) and MLP of every layer but the
      last;
    * ``served_logit_gap``: the widest gap by which a served token's
      logit lies below the reference's best, over every served token:
      the last layer, the LM head and the engine's token choice.

    With ``control``, the same numbers for the control put in the
    program's place: the reference with every GEMM input in float8 E4M3
    (the precision below the bfloat16 activations served), run on its own
    K/V rows, its token at each position the first by its own logits."""
    from bench import reference
    if not picked:
        return {"requests": 0, "tokens": 0}
    seqs, served = [], []
    for _, r, n_pos in picked:
        p, toks = r["item"].prompt, r["tokens"]
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        served.append((len(p), toks))
    tokens, pos, seg = reference.pack_sequences(seqs, packed)
    valid = seg < len(seqs)
    target = np.zeros(packed, bool)
    chosen = np.zeros(packed, np.int32)
    at = 0
    for s, (n_prompt, toks) in zip(seqs, served):
        rows = at + n_prompt - 1 + np.arange(len(toks))
        target[rows] = True
        chosen[rows] = toks
        at += len(s)
    out = {"requests": len(seqs), "tokens": int(target.sum())}

    def judge(side, rows, picks):
        st, gap = reference.forced_pass(weights, arch, tokens, pos, seg,
                                        rows, valid, picks)
        by_layer = _kv_rel_err(st)
        differ = np.asarray(st)[:, 4:].max(axis=1).tolist()
        gap = np.asarray(gap)[target]
        return {f"{side}kv_rel_err": max(by_layer),
                f"{side}kv_rel_err_by_layer": by_layer,
                f"{side}kv_differ_share": max(differ),
                f"{side}kv_differ_share_by_layer": differ,
                f"{side}served_logit_gap": float(gap.max()),
                f"{side}served_logit_gap_mean": float(gap.mean()),
                f"{side}served_disagree_share": float(np.mean(gap > 0))}

    out.update(judge("", tuple(reference.decode_kv(*x) for x in kv),
                     chosen))
    if control:
        ck, cv, top = reference.own_pass(weights, arch, tokens, pos, seg,
                                         "fp8")
        out.update(judge("control_", (ck, cv), np.asarray(top)))
    return out


def verdict(numbers: dict, limits: dict, prefix: str = "") -> dict:
    """Each compared number beside its limit."""
    return {n: {"value": numbers.get(prefix + n), "limit": lim}
            for n, lim in limits.items()}


def passes(v: dict) -> bool:
    return all(x["value"] is not None and x["value"] <= x["limit"]
               for x in v.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def build(cell: dict, ss_weights, ss_warmup):
    """Draw the weights, build the engine and serve the warm-up requests.
    Returns (weights, engine, client, time the weights were ready)."""
    import jax
    from repro.serving.engine import ServeEngine
    from bench import weights as wlib
    arch, eng = cell["config"]["arch"], cell["config"]["engine"]
    w0, w1 = ss_weights.generate_state(2)
    key = jax.random.fold_in(jax.random.key(int(w0), impl="rbg"), int(w1))
    weights = wlib.draw(key, arch, cell["config"]["weights"])
    jax.block_until_ready(weights)
    t_weights = time.time()
    engine = ServeEngine(
        _arch_config(arch), _program_params(weights, arch),
        batch_size=eng["slots"], max_len=eng["max_len"],
        kv_quant="mixfp4", kv_pool=eng["pool_pages"],
        kv_page_len=eng["page_len"], prefill_chunk=eng["prefill_chunk"],
        max_queue=eng["max_queue"])
    client = Client(engine)
    _warmup(client, loadgen.warmup(cell["traffic"], ss_warmup, arch["vocab"],
                                   WARMUP_UID0))
    return weights, engine, client, t_weights


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             cell: dict | None = None, require_tpu: bool = True,
             t_start: float = T_START, control: bool = False) -> dict:
    """One run of a cell; returns the result object.  ``control`` also
    puts the fp8 control in the program's place at the checked positions
    and judges it by the same limits (``diag["control_correct"]``;
    ``bench/control.py``, the benchmark's own runs do not)."""
    from repro.launch import compile_cache
    from bench import peaks as peaks_lib, trace as tr

    cell = spec.cell(name) if cell is None else cell
    cfg, traffic, cellf = cell["config"], cell["traffic"], cell["cell"]
    dev, n_dev = _device(cell["workload"]["chips"], require_tpu)
    t_init = time.time()
    compile_cache.enable()
    compiles = _Compiles()
    arch = cfg["arch"]
    ss_w, ss_t, ss_warm, ss_check = np.random.SeedSequence(seed).spawn(4)
    weights, engine, client, t_weights = build(cell, ss_w, ss_warm)
    engine_cfg = cfg["engine"]
    t_warm = time.time()
    trace_dir = str(OUT_DIR / "trace" / name)
    # the traffic's own set-up: ``preroll_seconds`` of arrivals fill the
    # slots before the window opens
    preroll = traffic["preroll_seconds"]
    items = loadgen.generate(traffic, preroll + seconds, ss_t, arch["vocab"])
    t_begin = time.time()
    t0 = t_begin + preroll
    trace_at = None
    if trace:
        length = min(traffic["trace_seconds"], 0.6 * seconds)
        trace_at = (t0 + 0.3 * seconds, t0 + 0.3 * seconds + length)
    tracer = _Tracer(client, trace_at, trace_dir)
    compiles.window = (t0, t0 + seconds)
    sent = _window(client, items, 0, t_begin, t0, _Tracer(client, None, None))
    at_open = _load(engine)
    _window(client, items, sent, t_begin, t0 + seconds, tracer)
    setup_s = t0 - t_start
    due_uids = [u for u, r in client.rec.items()
                if t0 <= r["due"] < t0 + seconds]
    e2e, failed, diag = end_to_end(client, due_uids, t0, seconds, setup_s)
    diag.update({"window_compiles": compiles.n,
                 "init_s": t_init - t_start,
                 "weights_s": t_weights - t_init,
                 "warmup_s": t_warm - t_weights,
                 "preroll_s": t0 - t_warm,
                 "at_open": at_open, "at_close": _load(engine),
                 "steps": len(client.steps)})
    diag.update(_step_times(client.steps, t0, t0 + seconds))
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev,
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": False, "attempted": len(due_uids), "failed": failed}
    if trace:
        trd = tr.load(trace_dir)
        device["busy_s"] = tr.busy_s(trd)
        device["window_s"] = tr.window_s(trd)
        pk = peaks_lib.peaks(dev.device_kind) if require_tpu else \
            cell.get("peaks")
        run = {"trace": trd, "arch": arch, "engine": engine_cfg,
               "peaks": pk,
               "steps": [s for s in client.steps if s["traced"]]}
        metrics = {}
        for m in cell["per_layer"]:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    result["device"] = device
    if trace:
        result["breakdown"] = tr.breakdown(trd)
    # what the check reads is gathered on the device; then the program's
    # state goes before the reference runs
    ck = cellf["check"]
    picked = sample(engine, client, ck["packed_tokens"], ss_check)
    kv = cache_rows(engine, picked, ck["packed_tokens"])
    del engine
    client.engine = None
    gc.collect()
    t_ref = time.time()
    chk = check(weights, arch, picked, kv, ck["packed_tokens"],
                control=control)
    compared = verdict(chk, ck["limits"])
    if control:
        diag["control_correct"] = passes(verdict(chk, ck["limits"],
                                                 "control_"))
    diag.update({f"check_{k}": v for k, v in chk.items()
                 if k not in ck["limits"]})
    diag["check_s"] = time.time() - t_ref
    result["correct"] = bool(passes(compared) and failed == 0)
    result["diag"] = diag
    result["check"] = {**compared,
                       "failed_requests": {"value": failed, "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _environment()
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        _log(f"bench: {e}")
        return 2
    _log(json.dumps({"diag": res["diag"]}))
    for k, v in res["check"].items():  # the numbers compared, last
        _log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
