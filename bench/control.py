"""Readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control-seeds 1,2,3]

One process runs the cell once per seed, as ``bench/run.py`` does, and
prints per seed the numbers ``check`` compares (the lower reading of each
is the largest over a dozen seeds or more).  For the seeds in
``--control-seeds`` it also puts the control in the program's place: the
reference with every GEMM input in float8 E4M3, the precision below the
bfloat16 activations the program serves, run on its own K/V rows over the
same prompts and served tokens, and judged by the same comparison and
limits (``control_correct``).  Its smallest reading of a number is that
number's upper reading.  The limits in ``bench/cells/<cell>.json`` lie
between the two.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run  # noqa: E402  (bench/ is the script's directory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench_run._environment()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = bench_run.run_cell(args.workload, seed, args.seconds, False,
                                 control=seed in ctl, t_start=time.time())
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "check": res["check"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            **res["diag"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
