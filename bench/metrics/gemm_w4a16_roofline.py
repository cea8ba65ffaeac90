"""Least time of every W4A16 GEMM call in the traced window, the larger of
operations over peak and bytes over bandwidth per call, over the kernel's
time in the trace."""
from bench import trace as tr
from bench.metrics import _costs


def _ideal(arch, m, peaks):
    t = 0.0
    for k, n in _costs.projection_shapes(arch):
        ops, nbytes = _costs.w4a16_call(m, k, n)
        t += max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return t * arch["n_layers"]


def read(run):
    busy = tr.kernel_s(run["trace"], "mixfp4_gemm_w4a16")
    if busy <= 0:
        return None
    arch, eng, peaks = run["arch"], run["engine"], run["peaks"]
    ideal = 0.0
    for s in run["steps"]:
        if s["decoded"]:
            ideal += _ideal(arch, eng["slots"], peaks)
        if s["prefill"][1] > s["prefill"][0]:
            ideal += _ideal(arch, eng["prefill_chunk"], peaks)
    return 100.0 * ideal / busy
