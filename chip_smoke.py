#!/usr/bin/env python3
"""Chip smoke test: serve gemma2-2b at its published widths on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded packed serving, four chips

One process drives the chip(s); it starts no child process.  On one chip
the phases are:

1. device: the backend is a TPU and the Pallas kernels compile natively
   (``ops.default_interpret()`` is False);
2. kernels: the packed GEMM and decode-attention kernels at gemma2-2b
   widths against the ``kernels/ref.py`` oracles, fused W4A4 against its
   two-dispatch composition and paged attention against fixed-slot;
3. serve: a ``ServeEngine`` over the full ``gemma2-2b`` config with seeded
   random weights, packed W4A16 projections and the packed MixFP4 KV
   cache serves the requests through ``submit``/``step``, twice (the
   first pass pays the compiles);
4. paged: the same requests through the paged KV pool, streams bitwise
   equal to the fixed-slot ones;
5. w4a4: the same requests with ``act_quant="mixfp4"`` (fused W4A4) and
   ``"mixfp4-2pass-rowscale"``, streams bitwise equal to each other;
6. logits: one decode step of the serving engine against the same step
   traced with every Pallas kernel replaced by its plain-jnp oracle, at
   the logits and layer by layer on the same layer inputs, beside a
   control layer that must fail the per-layer limit;
7. health: every request FINISHED, no degradation or retry counter moved,
   and the lowered decode step holds ``tpu_custom_call``.

``--four-chips`` runs only the sharded path: the same model on a 4-way
model axis (``make_host_mesh(model=4)``), its streams bitwise equal to a
single-device engine in this process (the docs/sharding.md contract).

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
or to ``.jax_cache`` in the checkout.  Exit status is 0 only if every phase
passed; then the last line of stdout is one JSON object naming the device.
With no TPU, or outside a checkout of this repository, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "gemma2-2b"
BATCH = 4
MAX_LEN = 512
NEW_TOKENS = 16
PROMPT_LENS = (32, 96, 160, 256)
PAGE_LEN = 16
PREFILL_CHUNK = 256     # one compiled prefill shape per engine
# Errors are relative to the largest reference magnitude.  A kernel against
# its exact plain-jnp oracle: both compute in f32 from the same bf16-exact
# operands (the CPU interpret tests hold the attention kernel to 1e-5).
KERNEL_TOL = 1e-3
# One gemma2-2b decode step against the kernel-free reference (PERF.md).
# The model's residual stream and GEMM inputs are bf16, so any difference
# between two compiled paths, however small, flips roundings that compound
# over the 26 layers: at the logits a sound path and one with a layer one
# scale ulp off both read about 1e-2.  The logit limit sits twice above
# that floor and catches gross faults.  The per-layer limit (each layer on
# the same input) sits between the sound path, whose worst layer reads
# about 1e-2 on a v5e (bf16 residual roundings flipped by f32-ulp
# differences), and a layer with its MLP down-projection scales one E4M3
# ulp high, which reads about 7.5e-2.
LOGIT_TOL = 2e-2
LAYER_TOL = 3e-2


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)
    log(f"ok: {what}")


def peak_hbm_gib() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------
def device_check(n_chips: int) -> dict:
    import jax
    from repro.kernels import ops
    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeFailure(f"JAX backend is {backend!r}, not a TPU")
    check(not ops.default_interpret(), "Pallas kernels compile natively")
    devs = jax.devices()
    check(len(devs) == n_chips, f"{n_chips} TPU chip(s) visible "
          f"({len(devs)} found)")
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    log(f"device: {info}")
    return info


# ---------------------------------------------------------------------------
# phase 2: kernels at gemma2-2b widths against the oracles
# ---------------------------------------------------------------------------
def kernel_phase(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import qtensor
    from repro.kernels import ops, ref
    from repro.models.base import KV_SCALE32

    key = jax.random.PRNGKey(seed)
    kx, kw, kq, kk, kv = jax.random.split(key, 5)
    m, k, n = 8, 2304, 9216
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = ops.pack_weight_qt(jax.random.normal(kw, (k, n)) * 0.02)
    y = qtensor.qmm(x, w)
    with jax.default_matmul_precision("highest"):
        want = _ref_gemm_w4a16(x, w.payload, w.scales, w.scale32)
    err = rel_err(y, want)
    check(err <= KERNEL_TOL, f"W4A16 {m}x{k}x{n} vs the dequantized f32 "
          f"matmul: rel err {err:.3e} <= {KERNEL_TOL}")
    fused = qtensor.qmm(x, w, fuse_act_quant=True, per_row_act=True)
    two = qtensor.qmm(qtensor.quantize_rows(x, pad_to=k, per_row=True), w)
    check(np.array_equal(np.asarray(fused), np.asarray(two)),
          "fused W4A4 == quantize_rows -> W4A4 bitwise")

    b, h, hkv, dh, s = BATCH, 8, 4, 256, 4096
    q = jax.random.normal(kq, (b, h, dh), jnp.float32)
    kp, ks, _ = ops.quantize_rows(
        jax.random.normal(kk, (b * s * hkv, dh)), scale32=KV_SCALE32)
    vp, vs, _ = ops.quantize_rows(
        jax.random.normal(kv, (b * s * hkv, dh)), scale32=KV_SCALE32)
    kp, vp = (a.reshape(b, s, hkv, dh // 2) for a in (kp, vp))
    ks, vs = (a.reshape(b, s, hkv, dh // 16) for a in (ks, vs))
    lengths = jnp.array([s, 3000, 1000, 17], jnp.int32)
    kw_ = dict(window=0, softcap=50.0, k_scale32=KV_SCALE32,
               v_scale32=KV_SCALE32)
    out = ops.attn_decode_packed(q, kp, ks, vp, vs, lengths, **kw_)
    with jax.default_matmul_precision("highest"):
        want = ref.ref_attn_decode_packed(q, kp, ks, vp, vs, lengths, **kw_)
    err = rel_err(out, want)
    check(err <= KERNEL_TOL, f"packed decode attention B={b} S={s} vs "
          f"ref_attn_decode_packed: rel err {err:.3e} <= {KERNEL_TOL}")
    # the same rows as a page pool: page p of sequence i is slab row
    # 1 + i*pages + p (row 0 is the trash page)
    pages = s // PAGE_LEN

    def pool(a):
        slab = a.reshape(b * pages, PAGE_LEN, *a.shape[2:])
        return jnp.concatenate([jnp.zeros_like(slab[:1]), slab])

    tables = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    paged = ops.attn_decode_paged(q, pool(kp), pool(ks), pool(vp), pool(vs),
                                  tables, lengths, **kw_)
    check(np.array_equal(np.asarray(paged), np.asarray(out)),
          "paged decode attention == fixed-slot bitwise")


# ---------------------------------------------------------------------------
# serving helpers
# ---------------------------------------------------------------------------
def make_prompts(cfg, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, n).astype(np.int32)
            for n in PROMPT_LENS]


def serve(engine, prompts, uid0: int) -> dict:
    """Drive ``engine`` the way ``launch/serve.py`` does: submit until the
    queue pushes back, then step.  Returns {uid: generated tokens}."""
    from repro.serving.engine import QueueFullError, Request
    pending = [Request(uid=uid0 + i, prompt=p, max_new_tokens=NEW_TOKENS)
               for i, p in enumerate(prompts)]
    streams = {r.uid: [] for r in pending}
    while pending or engine.has_work():
        while pending:
            try:
                engine.submit(pending[0])
            except QueueFullError:
                break
            pending.pop(0)
        for uid, tok in engine.step():
            streams[uid].append(int(tok))
    return streams


def timed_serve(name: str, engine, prompts, uid0: int) -> list:
    """Serve the prompts twice: the first pass compiles, the second is
    warm.  Both passes must give the same streams."""
    t0 = time.perf_counter()
    cold = serve(engine, prompts, uid0)
    t1 = time.perf_counter()
    warm = serve(engine, prompts, uid0 + 100)
    t2 = time.perf_counter()
    cold_s, warm_s = list(cold.values()), list(warm.values())
    n_tok = sum(len(s) for s in warm_s)
    log(f"{name}: cold pass {t1 - t0:.1f} s, warm pass {t2 - t1:.2f} s "
        f"-> compile about {(t1 - t0) - (t2 - t1):.1f} s; "
        f"{n_tok} tokens at {n_tok / (t2 - t1):.1f} tokens/s (warm, "
        f"host clock, includes prefill); peak HBM {peak_hbm_gib():.2f} GiB")
    check(cold_s == warm_s, f"{name}: warm streams equal cold streams")
    check(all(len(s) == NEW_TOKENS for s in cold_s),
          f"{name}: every request produced {NEW_TOKENS} tokens")
    return cold_s


def health(name: str, engine):
    rob = engine.robustness_report()
    states = rob["request_states"]
    check(set(states) == {"FINISHED"}, f"{name}: every request FINISHED "
          f"({states})")
    bad = {k: v for k, v in rob["counters"].items()
           if v and (k.startswith(("degraded_", "retries", "watchdog_"))
                     or k.startswith(("failed", "expired", "cancelled")))}
    check(not bad, f"{name}: no degradation/retry/failure counter moved "
          f"({bad or 'all 0'})")


def decode_args(engine, toks):
    import jax.numpy as jnp
    return (engine.params, jnp.asarray(toks, jnp.int32), engine.cache,
            jnp.asarray(engine.lengths.copy()))


def check_native_decode(name: str, engine):
    """The lowered decode step runs the Pallas kernels as TPU custom calls
    (not interpreted)."""
    import numpy as np
    with engine._mesh_ctx():
        text = engine._decode.lower(
            *decode_args(engine, np.zeros(engine.batch_size))).as_text()
    check("tpu_custom_call" in text,
          f"{name}: lowered decode step holds tpu_custom_call")


# ---------------------------------------------------------------------------
# phase 6: one decode step against the kernel-free reference
# ---------------------------------------------------------------------------
def _ref_quantize_rows(x, *, scale32=None, per_row=False, **_):
    """``ops.quantize_rows`` from the ``core`` quantizer (no Pallas)."""
    import jax.numpy as jnp
    from repro.core import pack as pack_lib
    from repro.core import quantize as Q
    from repro.core import scaling
    m, k = x.shape
    x = x.astype(jnp.float32)
    if per_row:
        s32 = (scaling.row_scale(x) if scale32 is None else
               jnp.broadcast_to(jnp.asarray(scale32, jnp.float32), (m,)))
        s = s32.reshape(m, 1, 1)
    else:
        s32 = (scaling.tensor_scale(x) if scale32 is None else
               jnp.asarray(scale32, jnp.float32))
        s = s32
    bq = Q.adaptive_block_quantize(x.reshape(m, k // 16, 16),
                                   Q.METHODS["mixfp4"], scale32=s)
    p = pack_lib.pack_blocks(bq)
    return p.payload.reshape(m, k // 2), p.scales.reshape(m, k // 16), s32


def _ref_gemm_w4a16(x, payload, scales, scale32, **_):
    """``ops.gemm_w4a16`` as the packed weight dequantized to f32 and one
    plain f32 matmul (x rounded to bf16 first, as the kernel rounds it)."""
    import jax.numpy as jnp
    from repro.kernels import ref
    w = ref.ref_dequant_weight_kn(payload, scales, scale32)
    return jnp.dot(x.astype(jnp.bfloat16).astype(jnp.float32), w)


@contextlib.contextmanager
def kernel_free():
    """Route the ``ops`` kernel entries a W4A16 decode step uses to
    plain-jnp oracles (at ``highest`` matmul precision) while it is
    traced.  Yields a list that names each oracle as a trace calls it."""
    import jax
    from repro.kernels import ops, ref
    traced = []

    def hi(f, name):
        def g(*a, **kw):
            traced.append(name)
            with jax.default_matmul_precision("highest"):
                return f(*a, **kw)
        return g

    attn_kw = ("window", "k_scale32", "v_scale32", "softcap")
    subs = {
        "quantize_rows": _ref_quantize_rows,
        "gemm_w4a16": _ref_gemm_w4a16,
        "attn_decode_packed":
            lambda q, kp, ks, vp, vs, ln, **kw: ref.ref_attn_decode_packed(
                q, kp, ks, vp, vs, ln,
                **{a: kw[a] for a in attn_kw if a in kw}),
        "attn_decode_paged":
            lambda q, kp, ks, vp, vs, bt, ln, **kw:
            ref.ref_attn_decode_packed(
                q, kp, ks, vp, vs, ln, block_tables=bt,
                **{a: kw[a] for a in attn_kw if a in kw}),
    }
    saved = {name: getattr(ops, name) for name in subs}
    try:
        for name, f in subs.items():
            setattr(ops, name, hi(f, name))
        yield traced
    finally:
        for name, f in saved.items():
            setattr(ops, name, f)


def check_kernel_free(name: str, traced: list, lowered):
    """The reference traced the GEMM and attention oracles and lowered to
    no Pallas kernel."""
    check({"gemm_w4a16", "attn_decode_packed"} <= set(traced),
          f"{name} traced the GEMM and attention oracles")
    check("tpu_custom_call" not in lowered.as_text(),
          f"{name} runs no Pallas kernel")


def bump_scales(lp):
    """One layer's parameters with every block scale of its MLP down
    projection one E4M3 ulp higher (zero and top codes kept): a subtly
    wrong layer."""
    import dataclasses
    import jax.numpy as jnp
    qt = lp["mlp"]["w_down"]
    e4m3 = qt.scales & 0x7F
    sc = jnp.where((e4m3 > 0) & (e4m3 < 0x7E), qt.scales + 1, qt.scales)
    return {**lp, "mlp": {**lp["mlp"],
                          "w_down": dataclasses.replace(qt, scales=sc)}}


def layer_errors(engine, toks):
    """Teacher-forced check of one decode step, layer by layer: layer l runs
    on the kernel path's own layer-l input, once with the Pallas kernels
    and once traced with their plain-jnp oracles.  A layer's error is the
    norm of the difference of the two outputs over the norm of the
    reference's residual update.  Returns the errors of every layer and
    that of the control: the middle layer's reference with
    :func:`bump_scales` applied."""
    import math
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.base import decode_positions
    model, ctx, cfg = engine.model, engine.ctx, engine.cfg
    lengths = jnp.asarray(engine.lengths.copy())
    tokens = jnp.asarray(toks, jnp.int32)
    positions = decode_positions(lengths, tokens.shape[0])
    windows = model.layer_windows()
    keys = jax.random.split(ctx.key, cfg.n_layers)

    def layer(lp, x, key, window, ck, cv):
        return model._layer_apply(
            lp, x, ctx.with_key(key), window, positions=positions,
            kv_cache=(ck, cv), cache_len=lengths)[0]

    def layer_args(l, x):
        def nth(tree):
            return jax.tree.map(lambda a: a[l], tree)
        return (nth(engine.params["layers"]), x, keys[l],
                jnp.int32(windows[l]), nth(engine.cache["k"]),
                nth(engine.cache["v"]))

    def err(y, want, x):
        y, want, x = (np.asarray(a, np.float64) for a in (y, want, x))
        return float(np.linalg.norm(y - want) / np.linalg.norm(want - x))

    x = engine.params["embed"][tokens[:, None]].astype(jnp.bfloat16)
    if cfg.emb_scale:
        x = x * math.sqrt(cfg.d_model)
    kernel = jax.jit(layer).lower(*layer_args(0, x)).compile()
    # a function of its own: jit would reuse the kernel path's trace of
    # ``layer`` and never call the oracles
    with kernel_free() as traced:
        lowered = jax.jit(lambda *a: layer(*a)).lower(*layer_args(0, x))
    check_kernel_free("reference layer", traced, lowered)
    reference = lowered.compile()
    errs, control = [], None
    for l in range(cfg.n_layers):
        a = layer_args(l, x)
        y, want = kernel(*a), reference(*a)
        errs.append(err(y, want, x))
        if l == cfg.n_layers // 2:
            control = err(reference(bump_scales(a[0]), *a[1:]), want, x)
        x = y
    return errs, control


def logits_phase(engine, prompts, uid0: int):
    """Admit the prompts, take two decode steps, then compare the engine's
    next decode step with the kernel-free reference on the same state:
    whole, at the logits, and layer by layer (:func:`layer_errors`)."""
    import jax
    import numpy as np
    from repro.serving.engine import Request
    probe_tokens = 32
    for i, p in enumerate(prompts):
        engine.submit(Request(uid=uid0 + i, prompt=p,
                              max_new_tokens=probe_tokens))
    for _ in range(probe_tokens // 2):
        if all(r is not None and len(r.generated) >= 2
               for r in engine.slots):
            break
        engine.step()
    check(all(r is not None and 2 <= len(r.generated) < probe_tokens
              for r in engine.slots), "probe: every request decoding")
    toks = [r.generated[-1] for r in engine.slots]
    args = decode_args(engine, toks)
    with engine._mesh_ctx():
        got, _ = engine._decode(*args)
    ctx, model = engine.ctx, engine.model
    step = jax.jit(lambda p, t, c, l: model.decode_step(p, t, ctx, c, l))
    with kernel_free() as traced:
        lowered = step.lower(*args)
    check_kernel_free("reference decode step", traced, lowered)
    want, _ = lowered.compile()(*args)
    got, want = np.asarray(got), np.asarray(want)
    err = rel_err(got, want)
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"logits: max |kernel - reference| / max |reference| = {err:.3e}, "
        f"max |reference| = {np.abs(want).max():.3f}, argmax agreement "
        f"{agree:.2f}")
    check(np.isfinite(got).all(), "decode logits finite")
    check(err <= LOGIT_TOL, f"decode logits within {LOGIT_TOL} of the "
          "kernel-free reference")

    errs, control = layer_errors(engine, toks)
    worst = int(np.argmax(errs))
    log(f"layers: |kernel - reference| / |reference update| worst "
        f"{errs[worst]:.3e} (layer {worst}), median {np.median(errs):.3e}; "
        f"control (layer {len(errs) // 2}, MLP down-projection scales an "
        f"E4M3 ulp high) {control:.3e}")
    check(errs[worst] <= LAYER_TOL,
          f"every layer within {LAYER_TOL} of its kernel-free reference")
    check(control > LAYER_TOL, f"layer control outside {LAYER_TOL}")
    while engine.has_work():
        engine.step()


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------
def init_params(cfg, seed: int):
    import jax
    from repro.models.base import build_model, param_count
    t0 = time.perf_counter()
    params, _ = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"{cfg.name}: {param_count(params) / 1e9:.3f} B parameters "
        f"initialised from seed {seed} in {time.perf_counter() - t0:.1f} s; "
        f"peak HBM {peak_hbm_gib():.2f} GiB")
    return params


def one_chip(seed: int):
    from repro import configs
    from repro.serving.engine import ServeEngine

    kernel_phase(seed)
    cfg = configs.full_config(ARCH)
    prompts = make_prompts(cfg, seed)
    params = init_params(cfg, seed)
    common = dict(batch_size=BATCH, max_len=MAX_LEN, kv_quant="mixfp4",
                  prefill_chunk=PREFILL_CHUNK)
    t0 = time.perf_counter()
    fixed = ServeEngine(cfg, params, **common)
    del params     # projections now live only as packed QTensors
    log(f"packed projections: {fixed.packed_bytes / 2**30:.2f} GiB "
        f"({fixed.compression:.2f}x smaller than bf16) in "
        f"{time.perf_counter() - t0:.1f} s; peak HBM after init + packing "
        f"{peak_hbm_gib():.2f} GiB")
    ref_streams = timed_serve("fixed-slot W4A16", fixed, prompts, 0)
    check_native_decode("fixed-slot W4A16", fixed)

    pool_pages = 1 + BATCH * MAX_LEN // PAGE_LEN
    paged = ServeEngine(cfg, fixed.params, kv_pool=pool_pages,
                        kv_page_len=PAGE_LEN, **common)
    check(timed_serve("paged W4A16", paged, prompts, 1000) == ref_streams,
          "paged streams == fixed-slot streams bitwise")
    check_native_decode("paged W4A16", paged)

    fused = ServeEngine(cfg, fixed.params, act_quant="mixfp4", **common)
    two = ServeEngine(cfg, fixed.params, act_quant="mixfp4-2pass-rowscale",
                      **common)
    check(timed_serve("fused W4A4", fused, prompts, 2000)
          == timed_serve("2-pass W4A4", two, prompts, 3000),
          "fused W4A4 streams == mixfp4-2pass-rowscale streams bitwise")
    check_native_decode("fused W4A4", fused)

    logits_phase(fixed, prompts, 4000)
    for name, eng in (("fixed-slot W4A16", fixed), ("paged W4A16", paged),
                      ("fused W4A4", fused), ("2-pass W4A4", two)):
        health(name, eng)
    log(f"peak HBM over the run {peak_hbm_gib():.2f} GiB")


def four_chips(seed: int):
    from repro import configs
    from repro.launch.mesh import make_host_mesh
    from repro.models.base import pack_projections
    from repro.serving.engine import ServeEngine

    cfg = configs.full_config(ARCH)
    prompts = make_prompts(cfg, seed)
    params = init_params(cfg, seed)
    packed, packed_bytes, _ = pack_projections(params)
    del params
    log(f"packed projections: {packed_bytes / 2**30:.2f} GiB; peak HBM "
        f"(chip 0) {peak_hbm_gib():.2f} GiB")
    common = dict(batch_size=BATCH, max_len=MAX_LEN, kv_quant="mixfp4",
                  prefill_chunk=PREFILL_CHUNK)
    single = ServeEngine(cfg, packed, **common)
    want = timed_serve("single-device W4A16", single, prompts, 0)
    mesh = make_host_mesh(model=4)
    log(f"host mesh {dict(mesh.shape)}")
    sharded = ServeEngine(cfg, packed, mesh=mesh, **common)
    got = timed_serve("4-way sharded W4A16", sharded, prompts, 1000)
    check(got == want, "sharded streams == single-device streams bitwise")
    check_native_decode("4-way sharded W4A16", sharded)
    for name, eng in (("single-device", single), ("sharded", sharded)):
        health(name, eng)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    try:
        device = device_check(n_chips)
        from repro.launch import compile_cache
        log(f"compile cache at {compile_cache.enable()}")
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
