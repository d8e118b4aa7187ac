"""Fleet benchmark: sequential vs interleaved execution and a fleet-size
scaling sweep.

Two experiments, each configuration in a child process of its own (jax
jit caches are module- and process-level, so timing two configurations
in one process hands whichever runs second a fully warmed cache and
biases every ratio). The parent never touches JAX, so each child in
turn can hold the chip; the host/device block of the payload comes from
a child. The four-chip check of the mesh-sharded runtime is
``chip_smoke.py --chips 4``.

  comparison   the original 8-query / 3-camera mixed workload run
               sequentially (each executor's ``run()`` to completion —
               the pre-fleet serving model) and as one
               ``FleetScheduler`` with cross-query superbatched scoring
               issued eagerly while the tick loop runs.  Uncontended
               uplink, so both modes do identical simulated work — the
               delta is pure dispatch/batching efficiency.
  fleet_scaling  synthesized fleets (one camera per query, cloned from
               the corpus scenes with distinct seeds) at 8/32/128
               queries, fleet mode only, recording wall_s / dispatches /
               frames-per-dispatch / watermark fires / overlap and full
               ``dispatch_stats`` per point so regressions are
               attributable to a layer.

On single-core hosts the score/uplink overlap term is structurally
zero (device compute and the host tick loop timeshare one core), so
wall-clock ratios there reflect dispatch/batching efficiency only; the
payload records ``host.cpu_count`` and flags this.  ``overlap_host_s``
(host time spent serving ticks while score dispatches were in flight)
is measured either way and is non-zero whenever the bucket-complete
watermark fires eagerly.  ``train_steps`` is kept low: operator
training is identical compute in every mode and only dilutes what this
bench measures.

Writes ``BENCH_fleet.json`` at the repo root so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

CAMERAS = ("JacksonH", "Banff", "Miami")
# 8 mixed queries over 3 cameras (the ROADMAP fleet workload at CI scale)
WORKLOAD = [("JacksonH", "retrieval"), ("Banff", "retrieval"),
            ("Miami", "retrieval"), ("JacksonH", "tagging"),
            ("Banff", "tagging"), ("Miami", "count_max"),
            ("JacksonH", "count_max"), ("Banff", "count_avg")]
STEP_KW = {"retrieval": {"max_passes": 3}, "tagging": {},
           "count_max": {"max_passes": 3}, "count_avg": {}}

# fleet-size sweep: kinds cycle per camera; every camera is distinct
# (cloned spec + seed), so landmark stores, banks, and operator
# architectures vary across the fleet the way a real deployment's would
SWEEP_KINDS = ("retrieval", "count_max", "count_avg")
SWEEP_KW = {"retrieval": {"max_passes": 2}, "count_max": {"max_passes": 2},
            "count_avg": {}}


def _build_fleet(hours: float, train_steps: int):
    from repro.core import landmarks as lm
    from repro.core.fleet import make_executor
    from repro.core.hardware import YOLO_V3
    from repro.core.query import Query, make_env
    from repro.core.training import FrameBank
    from repro.core.video import QUERY_CLASS, Video, corpus

    videos = {n: Video(corpus(hours=hours)[n]) for n in CAMERAS}
    stores = {n: lm.build_landmarks(v, 30, YOLO_V3)
              for n, v in videos.items()}
    banks = {n: FrameBank(v) for n, v in videos.items()}

    def make(cam, kind):
        env = make_env(videos[cam], Query(kind, QUERY_CLASS[cam]),
                       stores[cam], bank=banks[cam],
                       train_steps=train_steps)
        ex = make_executor(env, full_family=False)
        if kind == "tagging":
            ex.levels = (30, 10, 1)
        return ex

    return make


def _synth_workload(n_queries: int, hours: float, train_steps: int):
    """One synthesized camera per query: corpus scenes cloned with
    fresh names and seeds, kinds cycled.  Returns ``[(qid, executor,
    step_kw)]`` — the fleet-size sweep's unit of work."""
    from repro.core import landmarks as lm
    from repro.core.fleet import make_executor
    from repro.core.hardware import YOLO_V3
    from repro.core.query import Query, make_env
    from repro.core.training import FrameBank
    from repro.core.video import QUERY_CLASS, Video, corpus

    bases = list(corpus(hours=hours).items())
    jobs = []
    for i in range(n_queries):
        base_name, base_spec = bases[i % len(bases)]
        spec = dataclasses.replace(base_spec, name=f"{base_name}-{i}",
                                   seed=base_spec.seed + 7919 * (i + 1))
        video = Video(spec)
        store = lm.build_landmarks(video, 30, YOLO_V3)
        kind = SWEEP_KINDS[i % len(SWEEP_KINDS)]
        env = make_env(video, Query(kind, QUERY_CLASS[base_name]), store,
                       bank=FrameBank(video), train_steps=train_steps)
        ex = make_executor(env, full_family=False)
        jobs.append((f"q{i}:{kind}", spec.name, ex, SWEEP_KW[kind]))
    return jobs


def _mode_stats(rt, wall):
    return {
        "wall_s": round(wall, 2),
        "dispatches": rt.calls,
        "frames_scored": rt.frames_scored,
        "frames_per_dispatch": round(
            rt.frames_scored / max(rt.calls, 1), 1),
        "compiled_fns": rt.n_compiled,
        "dispatch_stats": rt.dispatch_stats(),
    }


def _fleet_stats(rt, sched, guard, wall):
    """Everything the fleet path reports beyond the raw dispatch
    counters: watermark behaviour, measured overlap, mesh identity and
    any sharding fallbacks taken."""
    buckets = {s: len(v) for s, v in rt.shape_vocab().items()}
    # tracing-bound acceptance: per arch, traces never exceed the
    # dispatch-shape vocabulary used (each shape traces exactly once)
    for s, n in guard.traces_per_arch.items():
        assert n <= buckets.get(s, 0), \
            f"{s}: {n} traces > {buckets.get(s, 0)} shapes"
    return {
        **_mode_stats(rt, wall),
        "score_rounds": sched.stats["score_rounds"],
        "eager_dispatches": sched.stats["eager_dispatches"],
        "watermark_fires": sched.stats["watermark_fires"],
        "overlap_host_s": sched.stats["overlap_host_s"],
        "result_block_s": sched.stats["result_block_s"],
        "device_count": sched.stats["device_count"],
        "mesh_shape": sched.stats["mesh_shape"],
        "sharded": sched.stats["sharded"],
        "sharding_fallbacks": rt.sharding_fallbacks(),
        "traces_per_arch": guard.traces_per_arch,
        "buckets_per_arch": buckets,
        "group_max": sched.group_max,
    }


def _run_fleet(jobs) -> dict:
    """Run ``[(qid, camera, executor, kw)]`` through one FleetScheduler
    on a fresh (mesh-aware when >1 device) runtime, under TraceGuard."""
    from repro.core.fleet import FleetScheduler
    from repro.core.runtime import OperatorRuntime, TraceGuard, set_runtime
    from repro.launch.mesh import make_scoring_mesh

    mesh = make_scoring_mesh()
    rt = OperatorRuntime(mesh=mesh)
    prev = set_runtime(rt)
    try:
        sched = FleetScheduler(contended=False, runtime=rt, mesh=mesh)
        for qid, cam, ex, kw in jobs:
            sched.add(qid, cam, ex, **kw)
        t0 = time.perf_counter()
        with TraceGuard(rt) as guard:
            res = sched.run()
        wall = time.perf_counter() - t0
    finally:
        set_runtime(prev)
    return {
        "done_t": [res[qid].done_t for qid, _, _, _ in jobs],
        **_fleet_stats(rt, sched, guard, wall),
        "runtime_knobs": {
            "small_flops": rt.small_flops,
            "small_quant": rt.small_quant,
            "superbatch": rt.superbatch,
            "group_max": sched.group_max,
        },
    }


def run_mode(mode: str, hours: float, train_steps: int) -> dict:
    """One comparison mode, measured in this process (meant to be the
    only mode this process ever runs — see module docstring)."""
    from repro.core.runtime import OperatorRuntime, set_runtime

    make = _build_fleet(hours, train_steps)
    if mode == "sequential":
        rt = OperatorRuntime()
        prev = set_runtime(rt)
        try:
            execs = [make(cam, kind) for cam, kind in WORKLOAD]
            t0 = time.perf_counter()
            done = [ex.run(**STEP_KW[kind]).done_t
                    for ex, (cam, kind) in zip(execs, WORKLOAD)]
            wall = time.perf_counter() - t0
        finally:
            set_runtime(prev)
        return {"done_t": done, **_mode_stats(rt, wall)}
    jobs = [(f"q{i}-{cam}-{kind}", cam, make(cam, kind), STEP_KW[kind])
            for i, (cam, kind) in enumerate(WORKLOAD)]
    return _run_fleet(jobs)


def run_point(n_queries: int, hours: float, train_steps: int) -> dict:
    """One fleet-size sweep point: build + run, fleet mode only."""
    out = _run_fleet(_synth_workload(n_queries, hours, train_steps))
    out.pop("done_t")
    return {"queries": n_queries, "cameras": n_queries, **out}


def _emit(call: str, out_path: str, **kw):
    """A child's side of ``_subprocess``: run one configuration and
    write its result, with the host/device block, as JSON."""
    from benchmarks.common import host_meta
    from repro.launch import compile_cache

    compile_cache.enable()
    out = {"mode": run_mode, "point": run_point}[call](**kw)
    out["host"] = host_meta()
    Path(out_path).write_text(json.dumps(out))


def _subprocess(call: str, **kw) -> dict:
    from benchmarks.run import child_env

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    try:
        code = ("from benchmarks.bench_fleet import _emit; "
                f"_emit({call!r}, {out_path!r}, **{kw!r})")
        subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=child_env(), check=True)
        return json.loads(Path(out_path).read_text())
    finally:
        os.unlink(out_path)


def run_comparison(hours: float, train_steps: int) -> dict:
    """Sequential vs fleet, each in a fresh subprocess (cold jit
    caches, order-independent), cross-checking simulated results."""
    seq = _subprocess("mode", mode="sequential", hours=hours,
                      train_steps=train_steps)
    fleet = _subprocess("mode", mode="fleet", hours=hours,
                        train_steps=train_steps)
    assert fleet.pop("done_t") == seq.pop("done_t"), \
        "uncontended fleet must match sequential simulated completion"
    seq.pop("host")
    return {
        "host": fleet.pop("host"),
        "queries": len(WORKLOAD),
        "cameras": len(CAMERAS),
        "sequential": seq,
        "fleet": fleet,
        "speedup": round(seq["wall_s"] / max(fleet["wall_s"], 1e-9), 2),
        "dispatch_reduction": round(
            seq["dispatches"] / max(fleet["dispatches"], 1), 2),
    }


def run_scaling(sizes, hours: float, train_steps: int) -> list:
    """Fleet-size scaling curve: one subprocess per point."""
    curve = []
    for n in sizes:
        t0 = time.time()
        point = _subprocess("point", n_queries=n, hours=hours,
                            train_steps=train_steps)
        point.pop("host")
        point["subprocess_wall_s"] = round(time.time() - t0, 1)
        print(f"[bench] scaling point {n}q: wall_s={point['wall_s']} "
              f"dispatches={point['dispatches']} "
              f"frames/dispatch={point['frames_per_dispatch']} "
              f"eager={point['eager_dispatches']}", flush=True)
        curve.append(point)
    return curve


def main(profile_name: str = "standard"):
    from benchmarks.common import print_table
    quick = profile_name == "quick"
    hours = 0.25 if quick else 0.5
    # low on purpose: training is identical compute in both modes and
    # only dilutes the dispatch/batching delta this bench measures
    train_steps = 10 if quick else 20
    sweep_hours = 0.05 if quick else 0.1
    sweep_steps = 5 if quick else 10
    sizes = (8, 32, 128)

    comparison = run_comparison(hours, train_steps)
    scaling = run_scaling(sizes, sweep_hours, sweep_steps)

    rows = [dict(mode=m, **{k: comparison[m][k] for k in
                            ("wall_s", "dispatches", "frames_scored",
                             "frames_per_dispatch", "compiled_fns")})
            for m in ("sequential", "fleet")]
    print_table(
        f"Fleet: {comparison['queries']} queries / "
        f"{comparison['cameras']} cameras, sequential vs interleaved "
        f"(subprocess-isolated)", rows)
    print_table(
        "Fleet-size scaling (fleet mode, one camera per query)",
        [{k: p[k] for k in ("queries", "wall_s", "dispatches",
                            "frames_per_dispatch", "eager_dispatches",
                            "overlap_host_s")} for p in scaling])
    fleet = comparison["fleet"]
    print(f"[bench] fleet speedup: {comparison['speedup']}x wall-clock; "
          f"dispatch reduction: {comparison['dispatch_reduction']}x "
          f"({comparison['sequential']['dispatches']} -> "
          f"{fleet['dispatches']} calls, "
          f"{fleet['eager_dispatches']} issued eagerly, "
          f"watermarks {fleet['watermark_fires']})")
    host = comparison.pop("host")
    payload = {
        "benchmark": "fleet",
        "hours": hours,
        "train_steps": train_steps,
        "sweep": {"hours": sweep_hours, "train_steps": sweep_steps},
        "isolation": "subprocess-per-configuration",
        "host": host,
        **comparison,
        "fleet_scaling": scaling,
    }
    if host.get("cpu_count") == 1:
        payload["overlap_note"] = (
            "single-core host: score/uplink overlap is physically "
            "serialized (overlap_host_s measures host time with "
            "dispatches in flight, not concurrent execution), and "
            "eager dispatch makes the XLA compute thread timeshare "
            "the core with the tick loop — expect fleet-vs-sequential "
            "at or slightly below 1.0x here even though the dispatch "
            "structure is identical; multi-core hosts get the overlap")
        print("[bench] note: " + payload["overlap_note"])
    path = ROOT / "BENCH_fleet.json"
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[bench] wrote {path}")
    return payload


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "quick")
