"""The serving engine's own host spans in a profiler trace.

``ServeEngine.step`` marks each of its phases with a host span
``serve.<phase>`` (``repro.serving.metrics.span``) on the profiler's clock,
so the spans line up with the device's operations in the same trace.  Some
carry host values as arguments: ``serve.decode`` the rows it decodes
(``rows``), the cache rows the occupied slots hold (``held_tokens``) and
the pool pages reserved for them (``pages``); ``serve.chunk_prefill`` the
chunk's cursor (``start``) and its real tokens (``tokens``).

``host_events`` reads the benchmark's and the engine's spans, with their
arguments, from the host planes of an ``.xplane.pb``; it needs no TPU
plane, so a trace taken on the CPU reads the same way.  ``of_run`` finds
them for a traced run of ``bench/run.py``: the newest trace under
``.bench_out/trace`` whose ``bench.window`` is the run's window, clipped to
it.  A trace of a program without these spans reads as none.  The rest is
pure Python over ``[name, start_ns, dur_ns, {args}]`` lists.

    python3 bench/spans.py <trace directory> [--record FILE]

prints one JSON object: the host time in each phase, the host's own time
per step, and the longest device idle gaps, each named by the innermost
span that overlaps it most.  ``--record`` also writes two steps of the
trace (device operations and programs, host spans) as a gzipped JSON
event list, the form of ``bench/tests/data``.
"""
from __future__ import annotations

import glob
import os
import statistics
import sys
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_ROOT = ROOT / ".bench_out" / "trace"
PREFIXES = ("bench.", "serve.")
SYNC = "serve.sync."


def newest(directory) -> str:
    paths = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def host_events(path: str) -> list:
    """Every ``bench.*`` and ``serve.*`` event on the host planes of the
    trace at ``path``, as ``[name, start_ns, dur_ns, {args}]``, by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():   # the stats type warns when first used
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        args = (dict(ev.stats)
                                if ev.name.startswith("serve.") else {})
                        out.append([ev.name, ev.start_ns, ev.duration_ns,
                                    args])
    return sorted(out, key=lambda e: e[1])


def clip(events: list, w0, w1) -> list:
    """The spans that lie wholly inside the window, less the window."""
    return [e for e in events if e[0] != "bench.window"
            and w0 <= e[1] and e[1] + e[2] <= w1]


def of_run(run: dict, root=TRACE_ROOT) -> list:
    """The spans of a traced run's window (``run["trace"]["window"]``),
    kept in ``run["spans"]`` so the metrics read the trace once."""
    if "spans" not in run:
        w0, w1 = run["trace"]["window"]
        try:
            events = host_events(newest(root))
        except FileNotFoundError:
            events = []
        ours = any(e[0] == "bench.window" and e[1] == w0
                   and e[1] + e[2] == w1 for e in events)
        run["spans"] = clip(events, w0, w1) if ours else []
    return run["spans"]


def named(spans: list, name: str) -> list:
    return [s for s in spans if s[0] == name]


def steps(spans: list) -> list[tuple[list, list]]:
    """Each ``serve.step`` span with the ``serve.*`` spans inside it."""
    out = []
    inner = [s for s in spans
             if s[0].startswith("serve.") and s[0] != "serve.step"]
    for st in named(spans, "serve.step"):
        a, b = st[1], st[1] + st[2]
        out.append((st, [s for s in inner
                         if a <= s[1] and s[1] + s[2] <= b]))
    return out


def step_host_ms(spans: list) -> list[float]:
    """Per step, its span's time less the time of its ``serve.sync.*``
    spans, in ms: the host's own work in the step."""
    return [(st[2] - sum(c[2] for c in kids if c[0].startswith(SYNC))) / 1e6
            for st, kids in steps(spans)]


def name_gap(a, b, spans: list) -> str:
    """The span that overlaps ``[a, b)`` most; of equal overlaps the
    shorter, which is the innermost of nested spans."""
    best, key = "none", (0, 0)
    for name, s, d, *_ in spans:
        over = min(b, s + d) - max(a, s)
        if over > 0 and (over, -d) > key:
            best, key = name, (over, -d)
    return best


def idle_gaps(trace: dict, spans: list, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the device in the window of a
    reduced trace (``bench.trace``), longest first, each as ``[span,
    seconds]``."""
    from bench import trace as tr
    w0, w1 = trace["window"]
    edges = [w0] + [x for ab in tr.busy_intervals(trace) for x in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[name_gap(a, b, spans), (b - a) / 1e9] for a, b in gaps[:top]]


def summary(trace: dict, spans: list) -> dict:
    """Host seconds by span name, the host's own ms per step, and the
    longest idle gaps by phase."""
    by = {}
    for name, _, d, *_ in spans:
        by[name] = by.get(name, 0.0) + d / 1e9
    host = step_host_ms(spans)
    dec = named(spans, "serve.decode")
    return {"window_s": (trace["window"][1] - trace["window"][0]) / 1e9,
            "steps": len(host),
            "step_host_ms_p50": statistics.median(host) if host else None,
            "step_host_ms_max": max(host) if host else None,
            "decode_rows": [s[3].get("rows") for s in dec],
            "phase_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "idle_gaps": idle_gaps(trace, spans)}


def record(trace: dict, spans: list, n_steps: int = 2) -> dict:
    """The first ``n_steps`` consecutive steps that hold a chunk prefill,
    as a raw event list with ``spans`` (``bench/tests/data``)."""
    st = steps(spans)
    first = next((i for i, (_, kids) in enumerate(st)
                  if any(k[0] == "serve.chunk_prefill" for k in kids)), 0)
    picked = st[first:first + n_steps]
    if not picked:
        raise ValueError("the trace holds no serve.step span")
    a = picked[0][0][1]
    b = picked[-1][0][1] + picked[-1][0][2]

    def inside(s, d):
        return a <= s and s + d <= b

    return {"device": trace["device"],
            "ops": [[o["name"], o["start"], o["dur"]] for o in trace["ops"]
                    if inside(o["start"], o["dur"])],
            "modules": [m for m in trace["modules"] if inside(m[1], m[2])],
            "host": [["bench.window", a, b - a]] + [
                h[:3] for h in trace["host"] if inside(h[1], h[2])],
            "spans": [s for s in spans
                      if s[0].startswith("serve.") and inside(s[1], s[2])]}


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("directory")
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    from bench import trace as tr
    trd = tr.load(args.directory)
    spans = clip(host_events(newest(args.directory)), *trd["window"])
    print(json.dumps(summary(trd, spans)), flush=True)
    if args.record:
        with gzip.open(args.record, "wt") as f:
            json.dump(record(trd, spans), f)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
