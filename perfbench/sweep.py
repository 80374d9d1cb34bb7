#!/usr/bin/env python3
"""Find a cell's knee: serve its configuration and partition at several
group counts, one run per seed at each, all in one process, and print per
run the offered load and the paper's outcome (Table II).

    python3 perfbench/sweep.py --workload resnet18.overload \\
        --groups 1,2,3,4,5,6 --seconds 10 --seeds 7,8,9

A run passes when no HP job missed (finish - due > deadline), no LP
release was refused and under 2% of the admitted LP jobs missed. A point
passes when most of its seeds pass, and the knee is the most groups that
pass; the sweep stops after two points in a row fail. Results go to
standard output, one JSON object per run, and last one with the points
that passed and the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def outcome(reqs, window_s: float) -> dict:
    hp = [r for r in reqs if r.prio == "hp"]
    lp = [r for r in reqs if r.prio == "lp"]
    lp_in = [r for r in lp if r.admitted]
    hp_miss = sum(not r.met for r in hp)
    lp_refused = len(lp) - len(lp_in)
    lp_miss = sum(not r.met for r in lp_in) / max(len(lp_in), 1)
    return {"offered_jps": len(reqs) / window_s,
            "goodput_jps": sum(r.met for r in reqs) / window_s,
            "hp_due": len(hp), "hp_missed": hp_miss,
            "lp_due": len(lp), "lp_refused": lp_refused,
            "lp_miss_share": lp_miss,
            "passes": hp_miss == 0 and lp_refused == 0 and lp_miss < 0.02}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--groups", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="7,8,9")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    spec = harness.bench()
    wl = harness.workload(spec, args.workload)
    load = harness.load_json("traffic", f"{wl['traffic']}.json")
    seeds = [int(x) for x in args.seeds.split(",")]
    passed, failing = [], 0
    for g in (int(x) for x in args.groups.split(",")):
        if failing == 2:
            break
        votes = 0
        for seed in seeds:
            t = time.perf_counter()
            res = harness.run_cell(
                args.workload, seed, args.seconds, False, t_start=t,
                load={**load, "groups": g},
                log=lambda m: print(m, file=sys.stderr, flush=True))
            run = res["run"]
            got = outcome(run.reqs, run.window_s)
            votes += got["passes"]
            print(json.dumps({"groups": g, "seed": seed, "tasks": g * (
                load["hp_per_group"] + load["lp_per_group"]), **got,
                "correct": res["correct"],
                "logit_gap_mean": res["checks"]["logit_gap_mean"]["value"],
                "setup_s": run.setup_s,
                "memory_peak_bytes": res["memory_peak_bytes"]}), flush=True)
        if 2 * votes > len(seeds):
            passed.append(g)
            failing = 0
        else:
            failing += 1
    print(json.dumps({"passed": passed,
                      "knee": max(passed, default=None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
