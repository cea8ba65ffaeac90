"""Serving driver: bring up an arch on the local mesh and serve batched
requests through the continuous-batching engine (packed MixFP4 weights).

Usage (CPU demo):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --requests 4 --new-tokens 8

W4A4 serving (docs/serving.md) — activations quantized on the fly, every
projection through the W4A4 kernel (both operands on the wire format):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --act-quant mixfp4

Sharded packed serving dryrun (docs/sharding.md) — projections held as
model-axis-sharded QTensors, decode bitwise-identical to single-device:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --force-host-devices 2 --model-parallel 2
"""
from __future__ import annotations

import os
import sys

# --force-host-devices must take effect BEFORE jax initializes (device
# count locks at first init), so it is peeked off argv here — same pattern
# as launch/dryrun.py's module-top XLA_FLAGS override.  Both argparse
# spellings ('--force-host-devices 2' and '--force-host-devices=2') are
# accepted; malformed values are left for argparse to report properly.
def _peek_force_host_devices(argv) -> int | None:
    for i, a in enumerate(argv):
        val = None
        if a == "--force-host-devices" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--force-host-devices="):
            val = a.split("=", 1)[1]
        if val is not None:
            try:
                return int(val)
            except ValueError:
                return None
    return None


_n = _peek_force_host_devices(sys.argv)
if _n is not None:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", ""))

import argparse
import signal
import threading
import time

import jax
import numpy as np

from repro import configs
from repro.core import qtensor
from repro.core.qgemm import QuantConfig
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.base import build_model, param_count
from repro.serving.engine import QueueFullError, Request, ServeEngine
from repro.serving.faults import parse_faults


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--quant", default="mixfp4")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-pack", action="store_true",
                    help="serve dense bf16 weights through the simulated "
                         "qdq path instead of packed QTensors")
    ap.add_argument("--kv-quant", default=None, choices=["bf16", "mixfp4"],
                    help="hold the KV cache packed (mixfp4: 4.5 bits/value, "
                         "decode through the fused attention kernel); "
                         "default bf16")
    ap.add_argument("--act-quant", default=None,
                    choices=["bf16", "mixfp4", "mixfp4-2pass",
                             "mixfp4-2pass-rowscale", "mixfp4-qdq"],
                    help="W4A4 serving: quantize decode/prefill activations "
                         "on the fly (type-in-sign E4M3 block scales) and "
                         "run every projection through the W4A4 kernel — "
                         "both GEMM operands on the wire format.  'mixfp4' "
                         "fuses the PER-ROW quantizer into the kernel "
                         "prologue (ONE dispatch per projection; each "
                         "output row a pure function of its own "
                         "activations); 'mixfp4-2pass-rowscale' is the "
                         "explicit quantize_rows(per_row=True)->GEMM "
                         "composition it is bitwise-identical to; "
                         "'mixfp4-2pass' is the legacy per-tensor-scale "
                         "composition (batch-coupled; kept as the A/B "
                         "baseline); 'mixfp4-qdq' is the "
                         "dequantize-then-W4A16 debugging oracle; default "
                         "bf16 (W4A16)")
    ap.add_argument("--act-rht", action="store_true",
                    help="grouped random Hadamard transform on BOTH W4A4 "
                         "GEMM operands (weights rotated at pack time, "
                         "activations in the fused prologue — same "
                         "deterministic signs, so the rotation cancels in "
                         "the dot product while flattening quantization "
                         "outliers; requires --act-quant mixfp4 or "
                         "mixfp4-2pass-rowscale)")
    ap.add_argument("--kv-pool", type=int, default=0, metavar="PAGES",
                    help="serve the packed KV cache as a PAGES-page pool "
                         "with per-request block tables, copy-on-write "
                         "prefix caching (transformers) and LRU eviction "
                         "(serving.kvpool; requires --kv-quant mixfp4). "
                         "Page 0 is the trash page, so usable pages are "
                         "PAGES-1")
    ap.add_argument("--kv-page-len", type=int, default=16, metavar="ROWS",
                    help="rows per KV page (multiple of 16 — the MixFP4 "
                         "block — and must divide --max-len)")
    ap.add_argument("--prefill-buckets", default="auto",
                    choices=["auto", "pow2-64", "off"],
                    help="pad prompts up a pow-2/64-step length ladder so "
                         "admissions reuse one compiled prefill per bucket "
                         "instead of compiling per distinct prompt length "
                         "(transformer families; 'auto' enables it there "
                         "and disables it for SSM/hybrid)")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="TOKENS",
                    help="chunked-prefill scheduling (serving.scheduler): "
                         "split each admission's prefill into TOKENS-token "
                         "chunks interleaved with decode steps, so long "
                         "prompts never stall the decode batch by more "
                         "than the chunk budget (transformer families; "
                         "bitwise-identical to whole-prompt prefill and "
                         "ONE compiled prefill shape total; replaces "
                         "--prefill-buckets)")
    ap.add_argument("--http-port", type=int, default=None, metavar="PORT",
                    help="serve over HTTP instead of running the local "
                         "demo drive: POST /generate streams SSE token "
                         "frames (client disconnect cancels the request), "
                         "GET /metrics scrapes Prometheus text, "
                         "GET /healthz is liveness, GET /readyz readiness, "
                         "GET /resume/{uid} re-attaches to a recovered "
                         "stream (serving.server; port 0 binds an "
                         "ephemeral port).  SIGTERM drains gracefully")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="crash-safe serving: append-only CRC-per-record "
                         "request journal (admissions, per-step tokens, "
                         "terminal transitions) written through the "
                         "request state machine (serving.journal); a "
                         "restarted process passes --recover to rebuild "
                         "every in-flight stream bitwise")
    ap.add_argument("--journal-sync", default="batch",
                    choices=["always", "batch", "off"],
                    help="journal fsync policy: 'always' per record, "
                         "'batch' once per engine step (default), 'off' "
                         "OS-buffered.  Greedy decode re-derives tokens "
                         "lost to an unsynced tail, so 'batch' still "
                         "resumes bitwise")
    ap.add_argument("--recover", action="store_true",
                    help="replay --journal-dir on startup: every "
                         "non-terminal journaled request re-prefills its "
                         "prompt + token history into fresh slots/pages "
                         "and continues decode bitwise identical to the "
                         "uninterrupted run; clients re-attach at "
                         "GET /resume/{uid}")
    ap.add_argument("--drain-deadline-ms", type=float, default=10000.0,
                    metavar="MS",
                    help="graceful-drain budget on SIGTERM/Ctrl-C: stop "
                         "admissions (readyz flips to 'draining'), let "
                         "in-flight requests finish within MS, then "
                         "journal the ledger snapshot and exit")
    ap.add_argument("--startup-budget-s", type=float, default=60.0,
                    metavar="S",
                    help="exit nonzero if the engine worker never reaches "
                         "'ready' (answering calls) within S seconds of "
                         "HTTP bind — so an orchestrator's restart loop "
                         "sees a wedged startup instead of hanging")
    ap.add_argument("--max-queue", type=int, default=64, metavar="N",
                    help="bounded admission queue: submissions beyond N "
                         "waiting requests are rejected with backpressure "
                         "(typed reason 'queue_full') instead of growing "
                         "an unbounded backlog")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="default per-request deadline: a request not "
                         "FINISHED within MS of submission lands EXPIRED "
                         "(typed reason 'deadline'), its slot and pool "
                         "pages released")
    ap.add_argument("--ttft-budget-ms", type=float, default=None,
                    metavar="MS",
                    help="default time-to-first-token budget: a request "
                         "with no first token within MS lands EXPIRED "
                         "(reason 'ttft_deadline')")
    ap.add_argument("--inject-faults", default=None, metavar="SEED:SPEC",
                    help="deterministic seeded fault injection at the "
                         "engine's host/device boundaries, e.g. "
                         "'7:decode=nan@3,pool_acquire=deny@p0.1' "
                         "(serving.faults.parse_faults; sites prefill/"
                         "decode/cow_copy/pool_acquire/checkpoint_read/"
                         "journal_write/process_crash, "
                         "kinds error/transient/nan/slow/dispatch/deny). "
                         "The engine then runs on the injector's virtual "
                         "clock")
    ap.add_argument("--save-weights", default=None, metavar="DIR",
                    help="write the packed QTensor weight tree as a "
                         "checkpoint and exit")
    ap.add_argument("--model-parallel", type=int, default=0, metavar="N",
                    help="serve SHARDED packed weights on an N-way model "
                         "axis of the host mesh: payload/scales carry "
                         "model-axis NamedShardings, decode runs the W4A16 "
                         "kernel per shard (docs/sharding.md)")
    ap.add_argument("--force-host-devices", type=int, default=0, metavar="N",
                    help="fake N host devices (CPU demo of the sharded "
                         "path; consumed before jax init, see module top)")
    args = ap.parse_args(argv)

    # flag-conflict checks BEFORE the (expensive) model init
    if args.recover and not args.journal_dir:
        ap.error("--recover replays a request journal; give --journal-dir")
    if args.no_pack:
        if args.model_parallel:
            ap.error("--model-parallel serves sharded PACKED weights; "
                     "drop --no-pack")
        if args.act_quant in ("mixfp4", "mixfp4-2pass",
                              "mixfp4-2pass-rowscale", "mixfp4-qdq"):
            ap.error("--act-quant mixfp4 is the W4A4 path (both operands "
                     "packed); drop --no-pack")
    if args.act_rht and args.act_quant not in ("mixfp4",
                                               "mixfp4-2pass-rowscale"):
        ap.error("--act-rht rotates both W4A4 operands and needs the "
                 "per-row scales; use --act-quant mixfp4 or "
                 "mixfp4-2pass-rowscale")
        if args.save_weights:
            ap.error("--save-weights requires packed weights; drop --no-pack "
                     "(the checkpoint format is the packed QTensor tree)")

    print(f"[serve] compile cache at {compile_cache.enable()}")
    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.full_config(args.arch))
    cfg = cfg.replace(quant=QuantConfig(method=args.quant))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    print(f"[serve] {cfg.name}: {param_count(params)/1e6:.1f}M params, "
          f"quant={args.quant}")

    mesh = None
    if args.model_parallel:
        mesh = make_host_mesh(model=args.model_parallel)
        print(f"[serve] host mesh {dict(mesh.shape)}: sharded packed "
              f"serving (column-parallel projections, expert-sharded MoE "
              f"stacks; decode bitwise-identical to single-device)")
    injector = (parse_faults(args.inject_faults)
                if args.inject_faults else None)
    engine = ServeEngine(cfg, params, batch_size=args.batch,
                         max_len=args.max_len,
                         pack_weights=not args.no_pack,
                         kv_quant=args.kv_quant, act_quant=args.act_quant,
                         act_rht=args.act_rht,
                         mesh=mesh, prefill_buckets=args.prefill_buckets,
                         prefill_chunk=args.prefill_chunk or None,
                         kv_pool=args.kv_pool or None,
                         kv_page_len=args.kv_page_len,
                         max_queue=args.max_queue,
                         deadline_ms=args.deadline_ms,
                         ttft_budget_ms=args.ttft_budget_ms,
                         journal_dir=args.journal_dir,
                         journal_sync=args.journal_sync,
                         faults=injector)
    if args.journal_dir:
        stats = engine.journal.stats
        print(f"[serve] request journal at {args.journal_dir} "
              f"(sync={args.journal_sync}): {stats['records']} record(s) "
              f"on disk, {stats.get('truncated_bytes', 0)} torn/corrupt "
              f"byte(s) truncated")
    if injector is not None:
        print(f"[serve] fault injection armed: seed {injector.seed}, "
              f"{len(injector.rules)} rule(s); engine on the injector's "
              "virtual clock")
    del params  # projections now live ONLY as packed QTensors in the engine
    if mesh is not None:
        shards = sorted({
            str(leaf.payload.sharding.spec)
            for leaf in jax.tree.leaves(
                engine.params,
                is_leaf=lambda x: isinstance(x, qtensor.QTensor))
            if isinstance(leaf, qtensor.QTensor)})
        print(f"[serve] QTensor payload/scales NamedSharding specs: "
              f"{shards}")
    if engine.packed_bytes:
        kern = ("W4A4" if engine.act_quant in ("mixfp4", "mixfp4-2pass",
                                               "mixfp4-2pass-rowscale")
                else "W4A16")
        print(f"[serve] projection weights held as packed QTensors: "
              f"{engine.packed_bytes / 1024:.0f} KiB "
              f"({engine.compression:.2f}x smaller than bf16), served "
              f"through qmm -> {kern} kernels")
    if engine.act_quant == "mixfp4":
        print("[serve] W4A4 fused: the PER-ROW quantizer runs in the W4A4 "
              "kernel's prologue — ONE Pallas dispatch per projection, "
              "full FP4xFP4 MMA analog; each output row is a pure "
              "function of its own activations")
    elif engine.act_quant == "mixfp4-2pass-rowscale":
        print("[serve] W4A4 two-dispatch (per-row scales): "
              "quantize_rows(per_row=True) onto each weight's packed K "
              "grid, then the packed-operand W4A4 kernel (the fused "
              "path's bitwise oracle)")
    elif engine.act_quant == "mixfp4-2pass":
        print("[serve] W4A4 two-dispatch (LEGACY per-tensor scale): "
              "quantize_rows onto each weight's packed K grid, then the "
              "packed-operand W4A4 kernel — batch-coupled; kept as the "
              "A/B baseline for the per-row modes")
    elif engine.act_quant == "mixfp4-qdq":
        print("[serve] W4A4 qdq oracle: same wire bytes, decoded back to "
              "dense rows and served W4A16")
    if engine.act_rht:
        print("[serve] grouped RHT on both W4A4 operands: weights rotated "
              "at pack time, activations in the fused prologue (shared "
              "deterministic signs — the rotation cancels in the dot "
              "product, only quantization statistics change)")
    if engine.kv_quant == "mixfp4":
        # bf16 equivalent: K and V tensors at 2 bytes/value
        bf16_kib = (2 * 2 * engine.batch_size * engine.max_len
                    * cfg.n_layers * cfg.n_kv_heads * cfg.dh) / 1024
        print(f"[serve] packed MixFP4 KV cache: "
              f"{engine.kv_cache_bytes() / 1024:.0f} KiB "
              f"(bf16 would be {bf16_kib:.0f} KiB), decode reads it "
              f"through the fused attention kernel")
    if engine.prefill_chunk:
        print(f"[serve] chunked-prefill scheduler armed: admissions "
              f"prefill {engine.prefill_chunk} tokens/step interleaved "
              f"with decode (ONE compiled prefill shape; bitwise vs "
              f"whole-prompt)")
    if args.save_weights:
        engine.save_weights(args.save_weights)
        print(f"[serve] packed QTensor weights checkpointed to "
              f"{args.save_weights}")
        return

    if args.recover:
        rep = engine.recover()
        print(f"[serve] journal recovery: {rep['replayed_records']} "
              f"record(s) -> {rep['requests']} request(s) "
              f"({rep['already_terminal']} already terminal, "
              f"{rep['resumed']} resumed, {rep['finalized']} finalized "
              f"from history); resumed streams continue bitwise — "
              f"clients re-attach at GET /resume/{{uid}}")

    if args.http_port is not None:
        from repro.serving.server import ServingServer
        with ServingServer(engine, port=args.http_port) as srv:
            # startup budget: the worker loop must be spinning AND the
            # engine must answer a call (first step may be compiling)
            # before we call this process 'ready'; a wedged init exits
            # nonzero so a restart loop can see it
            t0 = time.time()
            ok = srv.worker.ready.wait(args.startup_budget_s)
            if ok:
                try:
                    srv.worker.call(
                        lambda eng: True,
                        timeout=max(0.1, args.startup_budget_s
                                    - (time.time() - t0)))
                except TimeoutError:
                    ok = False
            if not ok:
                print(f"[serve] FATAL: engine not ready within "
                      f"{args.startup_budget_s:.0f}s startup budget")
                sys.exit(1)
            print(f"[serve] HTTP front-end on http://127.0.0.1:{srv.port} "
                  f"— POST /generate (SSE token stream), GET /metrics "
                  f"(Prometheus), GET /healthz (liveness), GET /readyz "
                  f"(readiness), GET /resume/{{uid}}; SIGTERM or Ctrl-C "
                  f"drains gracefully")
            stop = threading.Event()
            signal.signal(signal.SIGTERM, lambda *_: stop.set())
            try:
                while not stop.wait(0.2):
                    pass
            except KeyboardInterrupt:
                pass
            print(f"[serve] draining (deadline "
                  f"{args.drain_deadline_ms:.0f} ms): admissions "
                  f"stopped, in-flight requests finishing")
            rep = srv.drain(args.drain_deadline_ms)
            status = "complete" if rep["drained"] else "hit deadline"
            print(f"[serve] drain {status}: {rep['completed']} request(s) "
                  f"finished, {len(rep['survivors'])} survivor(s) "
                  f"journaled for recovery")
        return

    rng = np.random.RandomState(args.seed)
    # pooled demos share a page-sized "system prompt" across requests so
    # the pool report below actually shows prefix hits
    shared = (rng.randint(0, cfg.vocab, args.kv_page_len).astype(np.int32)
              if args.kv_pool else np.zeros((0,), np.int32))
    pending = [Request(uid=i,
                       prompt=np.concatenate(
                           [shared,
                            rng.randint(0, cfg.vocab, 6).astype(np.int32)]),
                       max_new_tokens=args.new_tokens)
               for i in range(args.requests)]
    t0, n_tok = time.time(), 0
    # requests ride the bounded admission queue: submit until backpressure,
    # then step (step() itself pumps QUEUED requests into free slots,
    # expires deadlines, and crosses the fault boundaries)
    while pending or engine.has_work():
        while pending:
            try:
                engine.submit(pending[0])
            except QueueFullError:
                break
            pending.pop(0)
        n_tok += len(engine.step())
    dt = time.time() - t0
    print(f"[serve] {args.requests} requests, {n_tok} tokens, "
          f"{n_tok/max(dt,1e-9):.1f} tok/s")
    print(f"[serve] prefill compile cache: {engine.admissions} admissions "
          f"-> {engine.prefill_compiles} compiled lengths, "
          f"{engine.prefill_cache_hits} shape-cache hits "
          f"(buckets={engine.prefill_buckets or 'off'})")
    rep = engine.pool_report()
    if rep is not None:
        print(f"[serve] KV pool: {rep['pages_total']} pages x "
              f"{rep['page_len']} rows, peak concurrency "
              f"{engine.max_concurrent}; prefix hits {rep['prefix_hits']} "
              f"pages / {rep['prefix_hit_tokens']} tokens skipped, "
              f"{rep['cow_copies']} COW copies, {rep['evictions']} "
              f"evictions, {rep['alloc_failures']} admission deferrals; "
              f"final occupancy {rep['occupancy']:.2f} "
              f"({rep['pages_cached']} cached / {rep['pages_free']} free)")
    rob = engine.robustness_report()
    states = rob["request_states"]
    print(f"[serve] lifecycle: {states} "
          f"(queue bound {rob['queue']['max_queue']}, deadline "
          f"{args.deadline_ms or 'off'} ms, ttft budget "
          f"{args.ttft_budget_ms or 'off'} ms)")
    notable = {k: v for k, v in rob["counters"].items()
               if k.split(":")[0] in ("failed", "expired", "cancelled",
                                      "rejected") or k.startswith(
                   ("retries", "degraded", "deferred", "injected"))}
    if notable:
        print(f"[serve] robustness counters: {notable}")
    if injector is not None:
        print(f"[serve] injector fired {len(injector.log)} event(s): "
              f"{injector.summary()['by_kind']}")


if __name__ == "__main__":
    main()
