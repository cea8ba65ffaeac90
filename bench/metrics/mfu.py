"""Model FLOPs of every token the engine processed in the traced window,
over the window's length times the chip's bf16 peak."""
from bench import trace as tr
from bench.metrics import _costs


def read(run):
    arch, steps = run["arch"], run["steps"]
    tokens = emitted = 0
    contexts = []
    for s in steps:
        lo, hi = s["prefill"]
        tokens += (hi - lo) + len(s["decode_ctx"])
        contexts += list(range(lo + 1, hi + 1)) + s["decode_ctx"]
        emitted += s["emitted"]
    if not tokens:
        return None
    flops = _costs.model_flops(arch, tokens=tokens, contexts=contexts,
                               emitted=emitted)
    return 100.0 * flops / (tr.window_s(run["trace"])
                            * run["peaks"]["bf16_flops"])
