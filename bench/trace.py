"""Profiler trace: capture, reduction to a small event list, and metrics.

``capture`` wraps part of the measured window in ``jax.profiler`` and an
annotation ``bench.window`` on the host.  ``load`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` and keeps only what the metrics read:

* ``ops``: operations on the first TPU (the HLO instruction's name, start,
  duration), each with the index of the program execution that holds it;
  a loop's own event, which spans its body's operations, is left out;
* ``modules``: program executions on that TPU;
* ``host``: the benchmark's own host spans (``bench.*``) and the window.

Everything after ``load`` is pure Python over that event list, so it is
checked on a small recorded trace (``bench/tests/data``).  A kernel is
found by the name of the jitted JAX function that wraps its
``pallas_call``, which names its HLO instruction
(``%mixfp4_gemm_w4a16.8 = f32[512,5120] custom-call(...)``).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re

import jax

KERNELS = ("mixfp4_gemm_w4a16", "mixfp4_attn_decode_paged",
           "mixfp4_quant_rows")
HOST_PREFIX = "bench."


@contextlib.contextmanager
def capture(directory: str):
    """Trace the enclosed work into ``directory``."""
    os.makedirs(directory, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python call tracing slows the host
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span that shows in the trace (no cost without a trace)."""
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def _short(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", hlo)
    return m.group(1) if m else hlo[:64]


CONTAINERS = ("while",)


def load(directory: str) -> dict:
    """The newest trace under ``directory`` as a plain event list."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    device = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = plane.name
        if plane.name == device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [[ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events]
                elif line.name == "XLA Ops":
                    ops += [[_short(ev.name), ev.start_ns, ev.duration_ns]
                            for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events
                         if ev.name.startswith(HOST_PREFIX)]
    if device is None:
        raise ValueError("the trace holds no TPU plane")
    return reduce_events({"device": device, "ops": ops, "modules": modules,
                          "host": host})


def reduce_events(tr: dict) -> dict:
    """Clip to the window, sort, and tie each op to its program execution
    and its kernel."""
    win = [h for h in tr["host"] if h[0] == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    mods = sorted((m for m in tr["modules"] if w0 <= m[1] < w1),
                  key=lambda m: m[1])
    starts = [m[1] for m in mods]
    ops = []
    for name, start, dur in sorted(tr["ops"], key=lambda o: o[1]):
        if not w0 <= start < w1 or _group(name) in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, start) - 1
        mod = i if i >= 0 and start < mods[i][1] + mods[i][2] else -1
        ops.append({"name": name, "start": start, "dur": dur, "mod": mod,
                    "kernel": kernel_of(name)})
    return {"device": tr["device"], "window": (w0, w1), "ops": ops,
            "modules": mods,
            "host": sorted((h for h in tr["host"] if h[0] != "bench.window"
                            and h[1] < w1 and h[1] + h[2] > w0),
                           key=lambda h: h[1])}


def kernel_of(name: str) -> str | None:
    return next((k for k in KERNELS if k in name), None)


def busy_intervals(trace: dict) -> list[tuple[int, int]]:
    out = []
    for op in trace["ops"]:
        a, b = op["start"], op["start"] + op["dur"]
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def window_s(trace: dict) -> float:
    return (trace["window"][1] - trace["window"][0]) / 1e9


def busy_s(trace: dict) -> float:
    w1 = trace["window"][1]
    return sum(min(b, w1) - a for a, b in busy_intervals(trace)) / 1e9


def kernel_s(trace: dict, kernel: str) -> float:
    return sum(op["dur"] for op in trace["ops"] if op["kernel"] == kernel) / 1e9


def module_kinds(trace: dict) -> list[str]:
    """Each program execution as ``decode`` (it runs the paged attention
    kernel), ``prefill`` (projection GEMMs without paged attention) or
    ``other``."""
    kinds = ["other"] * len(trace["modules"])
    seen = [set() for _ in trace["modules"]]
    for op in trace["ops"]:
        if op["mod"] >= 0 and op["kernel"]:
            seen[op["mod"]].add(op["kernel"])
    for i, ks in enumerate(seen):
        if "mixfp4_attn_decode_paged" in ks:
            kinds[i] = "decode"
        elif "mixfp4_gemm_w4a16" in ks:
            kinds[i] = "prefill"
    return kinds


def op_time_by_kind(trace: dict) -> dict:
    kinds = module_kinds(trace)
    out = {"decode": 0.0, "prefill": 0.0, "other": 0.0}
    for op in trace["ops"]:
        out[kinds[op["mod"]] if op["mod"] >= 0 else "other"] += op["dur"] / 1e9
    return out


def _group(name: str) -> str:
    return re.sub(r"[.\d]+$", "", name)


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by the benchmark's host span that overlaps them most."""
    by = {}
    for op in trace["ops"]:
        g = op["kernel"] or _group(op["name"])
        by[g] = by.get(g, 0.0) + op["dur"] / 1e9
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    w0, w1 = trace["window"]
    busy = busy_intervals(trace)
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, over = "none", 0
        for name, s, d in trace["host"]:
            o = min(b, s + d) - max(a, s)
            if o > over:
                best, over = name, o
        named.append([best, (b - a) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
