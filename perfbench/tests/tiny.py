"""A cell at a size a CPU test can hold: ResNet-18's layout at width 8 on
64x64 images, one group of tasks on two contexts, a one-second window."""
import time

import harness

WORKLOAD = "resnet18.overload"


def config():
    cfg = harness.load_json("configs", "resnet18.json")
    cfg.update(base_width=8, stage_widths=[8, 16, 32, 64], image_size=64,
               num_classes=10)
    return cfg


def load():
    tr = harness.load_json("traffic", "rn18_overload.json")
    tr.update(groups=1, contexts=2, oversubscription=2.0, warmup_s=0.5,
              grace_ms=300.0, trace_s=1.0, sample_jobs=8)
    return tr


def run(seed=2**33 + 5, trace=False, **kw):
    return harness.run_cell(WORKLOAD, seed, 1.0, trace,
                            t_start=time.perf_counter(),
                            require_chip=False, config=config(),
                            load=load(), log=lambda m: None, **kw)
