"""Least time of every paged decode-attention call in the traced window
(packed K/V rows read once up to each row's length, within the window),
over the kernel's time in the trace."""
from bench import trace as tr
from bench.metrics import _costs


def read(run):
    busy = tr.kernel_s(run["trace"], "mixfp4_attn_decode_paged")
    if busy <= 0:
        return None
    arch, eng, peaks = run["arch"], run["engine"], run["peaks"]
    ideal = 0.0
    for s in run["steps"]:
        if not s["decoded"]:
            continue
        ctx = [_costs.attended(arch, c) for c in s["decode_ctx"]]
        ctx += [1] * (eng["slots"] - len(ctx))     # idle lanes read one row
        ops, nbytes = _costs.attn_decode_step(arch, ctx)
        ideal += max(ops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / busy
