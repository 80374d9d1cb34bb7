"""Serving launcher: DARIS over partitions of the local device set.

By default it serves one HP and one LP ResNet-18 task at the published
size (base width 64, 1000 classes, 224x224x3 inputs), each at Table II's
30 JPS, with real jitted stage programs on wall clock:

    PYTHONPATH=src python -m repro.launch.serve --contexts 2 --os 2.0 \\
        --seconds 4 --dnns resnet18,unet

With several local devices, context k runs on device k: a job that moves
between contexts at a stage boundary has its inter-stage state resharded
onto the other chip (zero-delay migration).
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import TYPE_CHECKING, Callable, List, Optional, Sequence, Tuple

from ..serving.requests import TABLE2

if TYPE_CHECKING:
    from ..api import ServerConfig
    from ..core.task import TaskSpec

_CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache; entry points call this
    before their first compile. JAX reads ``JAX_COMPILATION_CACHE_DIR``
    itself. Without it the cache lives at the fixed ``<checkout>/.jax_cache``,
    so the next run from this checkout finds it. Every program is cached,
    however short its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def realtime_config(models: Sequence, *, contexts: int = 2,
                    streams: int = 1, oversub: float = 2.0,
                    seconds: float = 4.0,
                    jps: float = TABLE2["resnet18"][2], hw: int = 224,
                    input_factory: Optional[Callable] = None
                    ) -> Tuple[ServerConfig, List[TaskSpec]]:
    """The realtime server this launcher and ``chip_smoke.py`` serve: one
    HP and one LP task per staged CNN in ``models`` (tagged by position),
    each calibrated on this machine. Context k is pinned to local device k
    when there are several. Returns the unbuilt config, so callers can add
    fault plans or decision logging, and the task specs."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from ..api import HP, LP, DeviceModel, ServerConfig
    from ..serving.engine import staged_cnn_taskspec

    devices = jax.local_devices()[:contexts]
    # the device model below counts one unit per context; a stage program
    # can occupy the whole device, so it saturates at all of them
    specs = [staged_cnn_taskspec(model, priority=prio, jps=jps, input_hw=hw,
                                 tag=f"-{tag}{i}", n_sat=float(contexts),
                                 devices=devices)
             for i, model in enumerate(models)
             for prio, tag in ((HP, "hp"), (LP, "lp"))]
    shardings = ({k: SingleDeviceSharding(d) for k, d in enumerate(devices)}
                 if len(devices) > 1 else None)
    cfg = (ServerConfig.realtime()
           .tasks(specs)
           .contexts(contexts).streams(streams)
           .oversubscribe(oversub)
           .device(DeviceModel(n_units=float(contexts)))
           .horizon_ms(seconds * 1000.0)
           .phase_offsets(False)
           .realtime_io(input_hw=hw, input_factory=input_factory,
                        ctx_shardings=shardings))
    return cfg, specs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--contexts", type=int, default=2)
    ap.add_argument("--streams", type=int, default=1)
    ap.add_argument("--os", type=float, default=2.0, dest="oversub")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--dnns", default="resnet18")
    ap.add_argument("--jps", type=float, default=TABLE2["resnet18"][2])
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    use_compile_cache()
    from ..models.cnn import BUILDERS

    models = [BUILDERS[name]() for name in args.dnns.split(",")]
    cfg, _ = realtime_config(models, contexts=args.contexts,
                             streams=args.streams, oversub=args.oversub,
                             seconds=args.seconds, jps=args.jps, hw=args.hw)
    server = cfg.build()
    sched = server.scheduler
    if args.ckpt:
        from ..checkpoint import load_scheduler_state
        if os.path.exists(args.ckpt):
            load_scheduler_state(sched, args.ckpt)
            print(f"resumed scheduler state from {args.ckpt} "
                  f"(AFET cold-start skipped)")
    m = server.run()
    s = m.summary()
    print(f"JPS {s['jps']:.1f} | DMR HP {s['dmr_hp']:.1%} LP {s['dmr_lp']:.1%}"
          f" | resp HP {s['resp_hp']['mean']:.1f}ms LP "
          f"{s['resp_lp']['mean']:.1f}ms | rejected LP {s['rejected_lp']}")
    if args.ckpt:
        from ..checkpoint import save_scheduler_state
        save_scheduler_state(sched, args.ckpt)
        print(f"scheduler state saved -> {args.ckpt}")


if __name__ == "__main__":
    main()
