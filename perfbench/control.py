#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's ``logit_gap_mean`` and the
control's, seed by seed, in one process.

    python3 perfbench/control.py --workload resnet18.overload \\
        --seeds 11,12,13 --seconds 3

Each seed is a whole run of the cell at its own load, with a short
window, made with ``run_cell(control=True)``: after the window the plain
reference computed in bfloat16, the precision below the configuration's
float32, takes the sampled jobs' served logits' place, and the harness
decides ``correct`` on it (the control's reading, which has to come out
not correct). The served logits of the same jobs give the program's
reading against the same reference at the configuration's precision.
Each is given as the mean over the jobs (the number compared) and the
worst job; beside them, for information, the gaps to the reference at
"highest" precision. The limit in ``limits/<config>.json`` has to lie
above every program reading and below every control reading. One JSON
object per seed.
"""
import time

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import check
    import harness

    for seed in (int(x) for x in args.seeds.split(",")):
        res = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t_start=time.perf_counter(), control=True,
            log=lambda m: print(m, file=sys.stderr, flush=True))
        ref, weights, pool = res["reference"], res["weights"], res["pool"]
        got = {"logit_gap": [], "control_gap": [], "logit_gap_highest": [],
               "control_gap_highest": []}
        for s in res["samples"]:
            highest = ref.logits("highest", weights[s["task"]],
                                 pool[s["img"]])
            got["logit_gap"].append(check.gap(s["served"], s["reference"]))
            got["control_gap"].append(check.gap(s["logits"], s["reference"]))
            got["logit_gap_highest"].append(check.gap(s["served"], highest))
            got["control_gap_highest"].append(check.gap(s["logits"],
                                                        highest))
        print(json.dumps({
            "seed": seed, "jobs": len(res["samples"]),
            "control_correct": res["correct"],
            "control_decided": res["checks"]["logit_gap_mean"]["value"],
            **{f"{k}_mean": sum(v) / len(v) if v else None
               for k, v in got.items()},
            **{f"{k}_worst": max(v, default=None) for k, v in got.items()},
            "control_gap_least": min(got["control_gap"], default=None)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
