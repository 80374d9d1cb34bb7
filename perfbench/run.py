#!/usr/bin/env python3
"""The on-chip benchmark of DARIS serving staged CNNs under deadlines.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same checks are the last lines of standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402 — the set-up clock starts before the imports
import faulthandler  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
WATCHDOG_S = 340   # a hung run dumps every thread's stack and exits 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def metrics_for(spec: dict, trace: bool, run) -> dict:
    """The end-to-end metrics, or the per-layer ones when traced; a reader
    that finds nothing to read leaves its metric out."""
    import harness

    out = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = harness.load_module("metrics", m["name"]).read(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    try:
        res = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"perfbench: {e}")
        return 2
    spec = harness.bench()
    run = res["run"]
    devs = res["devices"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"],
           "metrics": metrics_for(spec, bool(args.trace), run),
           "device": device}
    if args.trace:
        t = run.trace
        if t is not None:
            device["busy_s"] = t.device["busy_s"]
            device["window_s"] = t.device["window_s"]
            out["breakdown"] = {"device_ops": t.device["device_ops"],
                                "idle_gaps": t.device["idle_gaps"]}
        else:
            log("perfbench: the trace held no window or no device program")
    out["checks"] = {k: {"value": c["value"] if math.isfinite(c["value"])
                         else None, "limit": c["limit"]}
                     for k, c in res["checks"].items()}
    reqs = run.reqs
    for prio in ("hp", "lp"):
        mine = [r for r in reqs if r.prio == prio]
        log(f"{prio}: due {len(mine)}, admitted "
            f"{sum(r.admitted for r in mine)}, finished "
            f"{sum(r.finish is not None for r in mine)}, met "
            f"{sum(r.met for r in mine)}")
    lags = sorted(r.release - r.due for r in reqs if r.release is not None)
    if lags:
        log(f"engine loop: longest release lag {lags[-1]:.1f} ms, "
            f"{sum(x > 100.0 for x in lags)} releases taken over 100 ms "
            f"late")
    print(json.dumps(out), flush=True)
    for name, c in res["checks"].items():
        rule = "<=" if name in ("logit_gap_mean", "payload_errors") \
            else ">="
        log(f"check {name} {c['value']} {rule} {c['limit']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
