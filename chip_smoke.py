"""Bring-up check: the served query path on a TPU, through its entry points.

    python chip_smoke.py             # phases a-d, one chip
    python chip_smoke.py --chips 4   # the mesh phase only, four chips

Phases run in order, in this one process (a chip belongs to one process
at a time); a failing phase raises and the script exits non-zero.

  a. device check: JAX must see a TPU, otherwise exit non-zero before
     any result is printed.
  b. scoring at full width: the widest operator of the family,
     L5c32d64s100, scores 1,024 seeded crops through each dispatch layer
     of ``OperatorRuntime`` (small, bucketed, an (8, 128) superbatch of
     eight parameter sets) on the Pallas kernel, compiled (not
     interpreted), against the ``kernels/ref`` stack at HIGHEST matmul
     precision.
  c. main path: the mixed 8-query / 3-camera workload of
     ``benchmarks/bench_fleet.WORKLOAD`` through ``FleetService`` with
     the full operator family, 150 training steps, a contended uplink
     and the default ``OracleService``; every query must reach a final
     answer.
  d. determinism: c again with a fresh service and runtime; every
     ``Progress`` must be identical. For information, one query served
     alone by the fleet is compared with its standalone ``run()``.

``--chips 4`` first scores b's superbatch with its group axis sharded
over ``make_scoring_mesh()`` and requires it bitwise equal to one
device; then it runs c over the mesh and without one, in this process,
and requires equal ``Progress``. The last line of stdout is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0
N_CROPS = 1024
GROUP = 8
HOURS = 0.25                 # 900 frames per camera at 1 fps
# presence probability: absolute; count: relative to max(1, |count|)
TOL_PROB = 1e-3
TOL_COUNT = 1e-3

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase's result is wrong."""


def check(ok, msg: str) -> None:
    # not an assert statement: the checks must hold under ``python -O``
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (XLA and
    Mosaic), and the number of backend compiles, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == COMPILE_EVENTS[-1]

    def mark(self):
        return self.seconds, self.compiles


def check_device(chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"[a] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev.platform!r}")
    if len(devices) != chips:
        raise SystemExit(f"expected {chips} chip(s), JAX sees {len(devices)}")
    return dev


# -- b. scoring at full width ------------------------------------------------


def _reference(params, x):
    """The operator forward pass built from ``kernels/ref``."""
    import jax
    from repro.kernels import ref

    h = x
    for c in params["convs"]:
        h = ref.conv_scorer(h, c["w"], c["b"], 2)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["dense"]["w"] + params["dense"]["b"])
    out = h @ params["head"]["w"] + params["head"]["b"]
    return jax.nn.sigmoid(out[:, 0]), jax.nn.softplus(out[:, 1])


class _Crops:
    """A FrameBank stand-in serving pre-made crops by index."""

    def __init__(self, crops):
        self._crops = crops

    def crops(self, idxs, region, size):
        return self._crops[idxs]


def _widest():
    """L5c32d64s100, ``GROUP`` seeded parameter sets and ``N_CROPS``
    seeded crops."""
    import jax
    import numpy as np
    from repro.core.operators import OperatorArch, init_operator

    arch = OperatorArch("smoke_L5c32d64s100", 5, 32, 64, 100)
    crops = np.random.default_rng(SEED).uniform(
        size=(N_CROPS, 100, 100, 3)).astype(np.float32)
    params = [init_operator(arch, jax.random.PRNGKey(SEED + g))
              for g in range(GROUP)]
    return arch, params, crops


def _superbatch(rt, arch, params, crops):
    """Member ``g`` scores the ``g``-th slice of ``crops`` with
    ``params[g]``, all in one ``(GROUP, N_CROPS // GROUP)`` dispatch."""
    import numpy as np

    per = N_CROPS // GROUP
    bank = _Crops(crops)
    demands = [(SimpleNamespace(arch=arch, params=params[g]), bank,
                np.arange(g * per, (g + 1) * per)) for g in range(GROUP)]
    out = rt.score_demands(demands, group_max=GROUP)
    return [np.concatenate([o[i] for o in out]) for i in range(2)]


def phase_scoring(clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.core.runtime import (OperatorRuntime, arch_signature,
                                    sig_flops, sig_str)

    arch, params, crops = _widest()
    sig = arch_signature(arch)
    rt = OperatorRuntime()
    log(f"[b] {sig_str(sig)} runtime backend={rt.backend} "
        f"interpret={rt.interpret}")
    check(rt.backend == "pallas" and rt.interpret is False,
          "a TPU host must score on the compiled Pallas kernel")
    # at this width the default threshold never picks the small layer
    small = OperatorRuntime(small_flops=2.0 * N_CROPS * sig_flops(sig))
    check(not rt.is_small(sig, N_CROPS) and small.is_small(sig, N_CROPS),
          "the two runtimes must take different layers")

    ref_fn = jax.jit(_reference)
    per = N_CROPS // GROUP
    with jax.default_matmul_precision("highest"):
        want0 = [np.asarray(a, np.float64)
                 for a in ref_fn(params[0], crops)]
        parts = [ref_fn(params[g], crops[g * per:(g + 1) * per])
                 for g in range(GROUP)]
    want_super = [np.concatenate([np.asarray(p[i], np.float64)
                                  for p in parts]) for i in range(2)]

    layers = [
        ("small", small, "small_calls",
         lambda: small.score_crops(params[0], arch, crops), want0),
        ("bucketed", rt, "bucketed_calls",
         lambda: rt.score_crops(params[0], arch, crops), want0),
        (f"superbatch({GROUP},{per})", rt, "super_calls",
         lambda: _superbatch(rt, arch, params, crops), want_super),
    ]
    for name, runtime, counter, score, (wp, wc) in layers:
        before = runtime.dispatch_stats()[counter]
        c0, _ = clock.mark()
        t0 = time.perf_counter()
        p, c = score()
        wall = time.perf_counter() - t0
        check(runtime.dispatch_stats()[counter] == before + 1,
              f"{name}: expected one {counter} dispatch")
        check(p.shape == c.shape == (N_CROPS,) and np.all(np.isfinite(p))
              and np.all(np.isfinite(c)), f"{name}: bad output")
        err_p = float(np.max(np.abs(p - wp)))
        err_c = float(np.max(np.abs(c - wc) / np.maximum(1.0, np.abs(wc))))
        log(f"[b] layer={name} max_abs_err_prob={err_p:.3e} "
            f"max_rel_err_count={err_c:.3e} wall_s={wall:.3f} "
            f"compile_s={clock.seconds - c0:.3f}")
        check(err_p <= TOL_PROB, f"{name}: prob error {err_p} > {TOL_PROB}")
        check(err_c <= TOL_COUNT, f"{name}: count error {err_c} > {TOL_COUNT}")


# -- c/d. the fleet ----------------------------------------------------------


def _world():
    from benchmarks.bench_fleet import CAMERAS
    from repro.core import landmarks as lm
    from repro.core.hardware import YOLO_V3
    from repro.core.video import Video, corpus

    specs = corpus(hours=HOURS)
    videos = {n: Video(specs[n]) for n in CAMERAS}
    return {n: (v, lm.build_landmarks(v, 30, YOLO_V3))
            for n, v in videos.items()}


def run_fleet(clock: CompileClock, tag: str, *, mesh=None,
              workload=None):
    """Serve ``workload`` (default: the bench_fleet mix) through a fresh
    FleetService on a fresh process-global runtime; returns
    ``{qid: Progress}`` after checking every query finished."""
    from benchmarks.bench_fleet import STEP_KW, WORKLOAD
    from repro.core.query import Query
    from repro.core.runtime import OperatorRuntime, set_runtime
    from repro.core.video import QUERY_CLASS
    from repro.serving.fleet import FleetService

    world = _world()
    set_runtime(OperatorRuntime())
    svc = FleetService(full_family=True, contended=True, mesh=mesh)
    for cam in sorted({cam for cam, _ in workload or WORKLOAD}):
        svc.register_camera(cam, *world[cam])
    qids = [svc.submit(cam, Query(kind, QUERY_CLASS[cam]), **STEP_KW[kind])
            for cam, kind in workload or WORKLOAD]
    c0, n0 = clock.mark()
    t0 = time.perf_counter()
    results = svc.run()
    wall = time.perf_counter() - t0
    rt = svc.scheduler.runtime
    for qid in qids:
        prog = results[qid]
        log(f"[{tag}] {qid} done_t={prog.done_t!r} "
            f"refinements={len(prog.points)} "
            f"op_switches={len(prog.op_switches)}")
        check(prog.done_t is not None and math.isfinite(prog.done_t),
              f"{qid} did not reach a final answer")
    log(f"[{tag}] wall_s={wall:.3f} compile_s={clock.seconds - c0:.3f} "
        f"backend_compiles={clock.compiles - n0} n_compiled={rt.n_compiled} "
        f"dispatch_stats={rt.dispatch_stats()}")
    return results, rt


def phase_determinism(clock: CompileClock, first) -> None:
    from benchmarks.bench_fleet import STEP_KW
    from repro.core.fleet import make_executor
    from repro.core.query import Query, make_env
    from repro.core.training import FrameBank
    from repro.core.video import QUERY_CLASS

    second, _ = run_fleet(clock, "d")
    diff = [q for q in first if first[q] != second[q]]
    check(not diff, f"Progress differs between identical runs: {diff}")
    log(f"[d] identical_progress=True queries={len(first)}")
    # information only: one query alone in the fleet vs standalone run()
    cam, kind = "JacksonH", "retrieval"
    fleet, _ = run_fleet(clock, "d-alone", workload=[(cam, kind)])
    video, store = _world()[cam]
    env = make_env(video, Query(kind, QUERY_CLASS[cam]), store,
                   bank=FrameBank(video), train_steps=150)
    alone = make_executor(env, full_family=True).run(**STEP_KW[kind])
    log(f"[d] fleet_vs_standalone {cam}/{kind} "
        f"bitwise_equal={next(iter(fleet.values())) == alone}")


def phase_mesh(clock: CompileClock) -> None:
    import numpy as np
    from repro.core.runtime import OperatorRuntime
    from repro.launch.mesh import make_scoring_mesh

    mesh = make_scoring_mesh()
    check(mesh is not None and mesh.size > 1, "mesh phase needs >1 chip")
    # the fleet's groups need not divide the mesh (those replicate), so
    # first a superbatch whose group axis does shard: GROUP=8 puts two
    # members on each of four chips
    arch, params, crops = _widest()
    rt = OperatorRuntime(mesh=mesh)
    c0, _ = clock.mark()
    t0 = time.perf_counter()
    got = _superbatch(rt, arch, params, crops)
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0
    want = _superbatch(OperatorRuntime(), arch, params, crops)
    equal = all(np.array_equal(g, w) for g, w in zip(got, want))
    log(f"[mesh] superbatch({GROUP},{N_CROPS // GROUP}) sharded "
        f"bitwise_equal_one_device={equal} "
        f"sharding_fallbacks={rt.sharding_fallbacks()} wall_s={wall:.3f} "
        f"compile_s={compile_s:.3f}")
    check(not rt.sharding_fallbacks(),
          f"a ({GROUP}, ...) superbatch must shard over {mesh.size} chips")
    check(equal, "sharded superbatch differs from one device")
    sharded, rt = run_fleet(clock, "mesh", mesh=mesh)
    single, _ = run_fleet(clock, "one-device")
    diff = [q for q in sharded if sharded[q] != single[q]]
    log(f"[mesh] mesh_info={rt.mesh_info()} "
        f"sharding_fallbacks={rt.sharding_fallbacks()} "
        f"dispatch_stats={rt.dispatch_stats()}")
    check(not diff, f"sharded Progress differs from one device: {diff}")
    log(f"[mesh] sharded_equals_one_device=True queries={len(sharded)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh phase, across four chips")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax

    dev = check_device(args.chips)
    cached = Path(cache_dir)
    log(f"[a] compile_cache={cache_dir} entries_at_start="
        f"{len(list(cached.iterdir())) if cached.is_dir() else 0}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_scoring(clock)
        first, _ = run_fleet(clock, "c")
        phase_determinism(clock, first)
    else:
        phase_mesh(clock)
    log(f"[done] total_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.seconds:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
