"""Record the small chip trace that ``test_trace.py`` reads.

    python chipbench/tests/record_trace.py <out_dir>

Scores 64 seeded crops with an L2c8d16s25 operator through the
runtime's bucketed layer (the Pallas ``conv_scorer`` on a TPU) and runs
three Adam steps, inside the harness's ``window``, ``run``, ``train``
and ``score`` annotations, then sleeps 50 ms inside ``loop`` so the
trace holds a known idle gap. Prints a summary of the planes, lines and
the busiest event names, the stats of a kernel event, and the program's
own ``diva.`` spans and the device time of each jitted program as
``xtrace.reduce`` reads them.
"""
from __future__ import annotations

import collections
import glob
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "chipbench"), str(ROOT / "src")]


def main(out: str) -> None:
    import jax
    import numpy as np
    import xtrace
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.core.operators import (OperatorArch, init_operator,
                                      train_operator)
    from repro.core.runtime import OperatorRuntime

    arch = OperatorArch("trace_L2c8d16s25", 2, 8, 16, 25)
    params = init_operator(arch, jax.random.PRNGKey(0))
    crops = np.random.default_rng(0).uniform(size=(64, 25, 25, 3)).astype(
        np.float32)
    labels = (np.arange(64) % 2).astype(np.float32)
    rt = OperatorRuntime(small_flops=0.0)
    rt.score_crops(params, arch, crops)          # compile outside the trace
    train_operator(arch, params, crops, labels, labels, steps=3, batch=32)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    with TraceAnnotation("window"):
        with TraceAnnotation("run"):
            with TraceAnnotation("score"):
                rt.score_crops(params, arch, crops)
            with TraceAnnotation("train"):
                p = train_operator(arch, params, crops, labels, labels,
                                   steps=3, batch=32)
                jax.block_until_ready(p)
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("trace", path, os.path.getsize(path))
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print("plane", repr(plane.name), [(ln.name, len(list(ln.events)))
                                          for ln in lines])
        for ln in lines:
            names = collections.Counter()
            first = None
            for ev in ln.events:
                names[ev.name] += ev.duration_ns
                first = first if first is not None else ev
            print("  line", repr(ln.name), "first_start_ns",
                  first.start_ns if first else None,
                  names.most_common(8))
            for ev in ln.events:
                st = list(ev.stats)
                if st:
                    print("    stats of", repr(ev.name), st[:12])
                    break
    red = xtrace.reduce(path, ("run", "train", "score"), "conv_scorer")
    for key in ("counts", "self_s", "total_s", "idle_by"):
        print("program", key, red["program"][key])
    print("module_s", red["module_s"])


if __name__ == "__main__":
    main(sys.argv[1])
