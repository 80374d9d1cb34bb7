#!/usr/bin/env python3
"""Smoke test of the realtime serving path on TPU at ResNet-18's published
size: base width 64, 1000 classes, 224x224x3 inputs, batch 1.

    python3 chip_smoke.py [--seed N]              # one chip
    python3 chip_smoke.py --chips 4 [--seed N]    # cross-chip migration only

One process runs every phase and starts no other.

(a) Device check: exit non-zero, before anything is built, unless JAX's
    devices are TPUs, at least ``--chips`` of them.
(b) Correctness: the four stage payloads of the served HP task run in
    sequence on the chip over seeded images. Their logits are compared with
    ``model.forward`` on the host CPU at "highest" matmul precision.
(c) Serving: ``ServerConfig.realtime()``, built as ``repro.launch.serve``
    builds it, serves HP and LP ResNet-18 at Table II's 30 JPS on two
    contexts for 5 s of wall clock. Both priorities complete jobs, no
    payload raises, every stage output is on a TPU, every served logit is
    within the tolerance of (b), and every submitted release is accounted
    for: submitted = completed + cancelled + rejected + aborted + pending.

With ``--chips 4`` only the four-chip path runs: four contexts, context k
on chip k, repartitioned mid-run between three and four contexts so that
jobs migrate between chips at stage boundaries. Stage outputs must appear
on all four chips, and the logits of every job whose stages ran on more
than one chip must equal, bit for bit, the same image run entirely on
chip 0.

Tolerance of (b): max |chip - reference| <= 3e-2 * max |reference|. TPU f32
convolutions and matmuls take one bf16 pass by default (8-bit mantissa,
unit roundoff 2^-9). Emulating that on the CPU (bf16 operands, f32
accumulation, every convolution) gave 4.2e-3 relative on this model at
seed 0. The bound leaves 7x room for the bf16 head and accumulation order;
a wrong program misses by O(1).

The last line of standard output is a JSON object naming the device. Every
time printed is a smoke reading, not a benchmark metric.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np

HW = 224
SERVE_S = 5.0
REL_TOL = 3e-2
N_IMAGES = 4
MIN_COMPLETED = 100
WATCHDOG_S = 900   # a hung run dumps every thread's stack and exits 1


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_check(chips: int) -> list:
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's devices are {devs[0].platform}")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    return devs


def build(seed: int, n_models: int, contexts: int, factory):
    from repro.launch.serve import realtime_config
    from repro.models.cnn import build_resnet
    model = build_resnet(18, seed=seed)
    cfg, specs = realtime_config([model] * n_models, contexts=contexts,
                                 seconds=SERVE_S, hw=HW,
                                 input_factory=factory)
    return model, cfg, specs


def tagged_input(image: np.ndarray):
    """Every job serves ``image``; its id rides along so that the stage
    records can be grouped by job."""
    def make(job):
        return {"job": np.int32(job.job_id), "x": image}
    return make


def observe(specs, log: list) -> None:
    """Wrap each served payload to record (job, stage, device, logits)."""
    for spec in specs:
        last = len(spec.stages) - 1
        for j, st in enumerate(spec.stages):
            st.payload = _observed(st.payload, j, j == last, log)


def _observed(payload, stage: int, last: bool, log: list):
    def run(state):
        out = payload(state["x"])
        log.append((int(state["job"]), stage, out.device,
                    np.asarray(out) if last else None))
        return {"job": state["job"], "x": out}
    return run


def run_stages(payloads, image: np.ndarray, device) -> np.ndarray:
    import jax
    x = jax.device_put(image, device)
    for p in payloads:
        x = p(x)
    return np.asarray(x)


def cpu_reference(model, images) -> list:
    import jax
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        params = jax.device_put(model.params, cpu)
        fwd = jax.jit(model.forward)
        return [np.asarray(fwd(params, jax.device_put(im, cpu)))
                for im in images]


def print_stages(specs) -> None:
    for spec in specs:
        for st in spec.stages:
            print(f"  {spec.name} {st.name}: first call (compile + run) "
                  f"{st.first_call_ms / 1000.0:.3f} s, calibrated t_alone "
                  f"{st.t_alone_ms:.3f} ms  [smoke reading]")


def serve(cfg, specs, log: list):
    observe(specs, log)
    server = cfg.record_decisions().build()
    m = server.run()   # raises if any payload raised
    s = m.summary()
    from repro.api import HP, LP
    for p, name in ((HP, "HP"), (LP, "LP")):
        r = s[f"resp_{name.lower()}"]
        print(f"serve {name}: completed {m.completed[p]}, DMR "
              f"{s[f'dmr_{name.lower()}']:.4f}, response p50 {r['p50']:.3f} "
              f"ms p99 {r['p99']:.3f} ms over {len(m.response_ms[p])} jobs  "
              f"[smoke reading]")
    print(f"serve: rejected_lp {s['rejected_lp']}, skipped_releases "
          f"{s['skipped_releases']}, migrations {s['migrations']}, "
          f"resharded {server.backend.resharded}")
    check(m.completed[HP] > 0 and m.completed[LP] > 0,
          f"a priority completed nothing: {dict(m.completed)}")
    check(sum(m.completed.values()) >= MIN_COMPLETED,
          f"completed {sum(m.completed.values())} < {MIN_COMPLETED}")
    platforms = {d.platform for _, _, d, _ in log}
    check(platforms == {"tpu"}, f"stage outputs on {platforms}")
    # every release that reached the scheduler logged exactly one of these
    submitted = sum(1 for d in server.decisions
                    if d.startswith(("admit ", "reject ", "batch ", "shed ")))
    acct = {"completed": sum(m.completed.values()),
            "cancelled": sum(m.cancelled.values()),
            "rejected": sum(m.rejected.values()),
            "aborted": sum(m.aborted.values()),
            "pending": sum(m.unfinished.values())}
    print(f"conservation: submitted {submitted} = "
          + " + ".join(f"{k} {v}" for k, v in acct.items()))
    check(submitted == sum(acct.values()), "conservation law broken")
    return server, m


def one_chip(seed: int, devs: list) -> None:
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((N_IMAGES, 1, HW, HW, 3)).astype(np.float32)
    model, cfg, specs = build(seed, 1, 2, tagged_input(images[0]))
    print_stages(specs)

    raw = [st.payload for st in specs[0].stages]
    chip = [run_stages(raw, im, devs[0]) for im in images]
    ref = cpu_reference(model, images)
    check(all(c.shape == (1, model.n_classes) and np.isfinite(c).all()
              for c in chip), "chip logits malformed")
    err = max(float(np.abs(c - r).max()) for c, r in zip(chip, ref))
    scale = max(float(np.abs(r).max()) for r in ref)
    check(scale > 0.0, "reference logits are all zero: nothing to compare")
    agree = [int(c.argmax() == r.argmax()) for c, r in zip(chip, ref)]
    decisive = [float(np.diff(np.sort(r[0])[-2:])[0]) > 2 * err for r in ref]
    print(f"correctness: max abs error {err:.6e}, max |ref| {scale:.6e}, "
          f"relative {err / scale:.6e} (tolerance {REL_TOL}), top-1 agree "
          f"{sum(agree)}/{N_IMAGES}")
    check(err <= REL_TOL * scale, "stage logits differ from the reference")
    check(all(a for a, d in zip(agree, decisive) if d),
          "top-1 differs where the reference margin exceeds 2x the error")

    log: list = []
    serve(cfg, specs, log)
    served = [x for _, _, _, x in log if x is not None]
    worst = max(float(np.abs(x - ref[0]).max()) for x in served)
    same = sum(np.array_equal(x, chip[0]) for x in served)
    print(f"served logits: {len(served)} jobs, max abs error vs reference "
          f"{worst:.6e}, bit-equal to phase (b) chip logits {same}/"
          f"{len(served)}")
    check(worst <= REL_TOL * scale, "served logits differ from the reference")


def four_chip(seed: int, devs: list) -> None:
    image = np.random.default_rng(seed).standard_normal(
        (1, HW, HW, 3)).astype(np.float32)
    _, cfg, specs = build(seed, 2, 4, tagged_input(image))
    print_stages(specs[:1])
    on_chip0 = run_stages([st.payload for st in specs[0].stages], image,
                          devs[0])
    # repartition between three and four contexts a few ms after
    # synchronized releases (every 500 ms is 15 periods), while jobs are
    # between stages: each time, a task whose chip changes carries its
    # in-flight job's state to the new chip at the next stage boundary
    for k in range(1, 9):
        cfg.reconfigure_at(500.0 * k + 2.0 * (1 + k % 4),
                           n_contexts=3 if k % 2 else 4)

    log: list = []
    server, _ = serve(cfg, specs, log)
    check(server.backend.resharded > 0, "no inter-stage state resharded")
    seen = {d for _, _, d, _ in log}
    print(f"stage outputs on {len(seen)} devices: "
          f"{sorted(d.id for d in seen)}")
    check(seen == set(devs[:4]), "stage outputs missing from some chips")
    trail, logits = defaultdict(set), {}
    for job, _, d, x in log:
        trail[job].add(d)
        if x is not None:
            logits[job] = x
    moved = [j for j in logits if len(trail[j]) > 1]
    equal = [j for j in moved if np.array_equal(logits[j], on_chip0)]
    every = sum(np.array_equal(x, on_chip0) for x in logits.values())
    print(f"migrated jobs completed {len(moved)}, bit-equal to chip 0 "
          f"{len(equal)}; all jobs bit-equal {every}/{len(logits)}")
    check(bool(moved), "no completed job ran on more than one chip")
    check(len(equal) == len(moved), "migrated logits differ from chip 0")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        devs = device_check(args.chips)
        sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
        from repro.launch.serve import use_compile_cache
        use_compile_cache()
        (four_chip if args.chips == 4 else one_chip)(args.seed, devs)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
