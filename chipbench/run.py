"""The chip benchmark of the served fleet path.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--control 1]

A cell of ``BENCHMARK.json`` names a configuration (``configs/``) and a
traffic mix (``mixes/``). The configuration's cloud detector serves
every query's verification; a detector whose entry names a
``reference`` brings its reference module (``detectors/``), whose
``install`` may wrap further calls for its counters and captures. A run:

  1. set-up: turns on JAX's persistent compilation cache (every compile
     is written), requires a TPU whose ``device_kind`` has peaks in
     ``peaks.json``, builds the cameras the mix queries (footage and
     capture-time landmarks) and serves one warm-up round, which
     compiles every shape the window uses;
  2. window: rounds back to back, each a fresh ``FleetService`` (fresh
     frame caches, the process-wide scoring runtime kept) that submits
     the mix's queries in the seed's order and runs them to their final
     answers. No round starts after ``--seconds``; the window is the
     span of the whole rounds it holds. With ``--trace 1`` the window
     is traced and the per-layer readers of ``layers/`` report, from the
     trace's reduction (``xtrace.py``: the harness's and the program's
     spans, device time by operation and by jitted program), the
     runtime's dispatch counts, the frames and samples counted, and the
     window's deltas of the probes' ``extra`` counters;
  3. check: ``check.py`` compares what the window produced with the
     plain reference (``reference.py``), after the device's peak memory
     has been read. ``--control 1`` puts the reference one precision
     below the configuration's in the program's place there, so that
     ``correct`` shows whether the limits catch it; the benchmark's own
     runs leave it off.

Earlier lines report set-up, rounds, counters and the compared numbers;
the last line of stdout is the result as one JSON object. Without a
TPU, or with a device kind that has no peaks, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# the benchmark's modules by bare name, the program from src/
sys.path[:1] = [str(HERE), str(ROOT / "src")]

import check  # noqa: E402
import probes as probes_mod  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
import xtrace  # noqa: E402

KERNEL = "conv_scorer"           # the Pallas call's device events
TRACE_DIR = ROOT / ".chipbench" / "trace"


def log(msg: str) -> None:
    print(msg, flush=True)


class NoChip(SystemExit):
    pass




def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the reference one precision below the "
                    "configuration's in the program's place in the check")
    return ap.parse_args(argv)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def device_check(jax, cell: dict):
    """The TPU and its peaks, or exit before anything is measured."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev.platform!r}")
    if len(devices) < cell["chips"]:
        raise NoChip(f"cell needs {cell['chips']} chip(s), JAX sees "
                     f"{len(devices)}")
    try:
        return devices, work.peaks(dev.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None


def serve_detector(det) -> None:
    """Build every query's env with the cloud detector ``det``:
    ``FleetService.submit`` calls ``make_env`` with its default
    (YOLOv3) and takes no detector of its own."""
    from repro.core import query
    from repro.serving import fleet

    fleet.make_env = functools.partial(query.make_env, cloud_det=det)


def run_round(world, cfg, queries, net):
    """One round: a fresh FleetService serves ``queries`` to their final
    answers. Returns per-query host times from submit."""
    from repro.core.query import Query
    from repro.serving.fleet import FleetService

    svc = FleetService(full_family=cfg["full_family"],
                       train_steps=cfg["train_steps"],
                       contended=cfg["contended"])
    for cam in dict.fromkeys(cam for cam, _, _ in queries):
        video, store, _cls = world[cam]
        svc.register_camera(cam, video, store)
    t_sub, first, last = {}, {}, {}
    t0 = time.perf_counter()
    for cam, kind, step in queries:
        t = time.perf_counter()
        qid = svc.submit(cam, Query(kind, world[cam][2]), net=net, **step)
        t_sub[qid] = t

    def on_progress(qid, _t, _v):
        now = time.perf_counter()
        first.setdefault(qid, now)
        last[qid] = now

    results = svc.run(on_progress=on_progress)
    t1 = time.perf_counter()
    fps = cfg["fps"]
    out = []
    for task in svc.scheduler.tasks:
        qid, prog = task.qid, results[task.qid]
        done = prog.done_t is not None and math.isfinite(prog.done_t)
        out.append(dict(qid=qid, done=done,
                        archive_s=task.env.n_frames / fps,
                        first_s=first.get(qid, t1) - t_sub[qid],
                        final_s=last.get(qid, t1) - t_sub[qid]))
    return dict(start=t0, end=t1, wall=t1 - t0, queries=out,
                tasks=svc.scheduler.tasks, stats=svc.scheduler.stats)


def load_readers(bench: dict, cell: str):
    """``{metric: (entry, read)}`` of the per-layer metrics this cell
    reports, each from ``layers/<metric>.py``."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        mod = traffic.load_module(HERE / "layers" / f"{m['name']}.py",
                                  f"chipbench_layer_{len(out)}")
        out[m["name"]] = (m, mod.read)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_of(bench, args.workload)
    cfg = traffic.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    limits = traffic.load_limits(args.workload)

    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".chipbench" / "tpu_logs"))
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    # write every compile to the cache, however short or small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices, peak = device_check(jax, cell)
    dev = devices[0]
    cache = Path(cache_dir)
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    t_jax = time.perf_counter()
    log(f"[setup] device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} cache={cache_dir} "
        f"cache_entries_at_start={entries}")

    from clock import CompileClock
    from repro.core.hardware import DETECTORS, NetworkModel
    from repro.core.runtime import get_runtime

    clock = CompileClock()
    pr = probes_mod.Probes()
    probes_mod.install(pr)
    dets = traffic.detectors(cfg)
    for mod in traffic.reference_modules(dets):
        if hasattr(mod, "install"):
            mod.install(pr)
    serve_detector(DETECTORS[cfg["cloud_detector"]])
    queries = traffic.round_queries(mix, args.seed)
    world = traffic.build_world(cfg, {cam for cam, _, _ in queries})
    net = NetworkModel(uplink_bytes_per_s=cfg["uplink_bytes_per_s"],
                       frame_bytes=cfg["frame_bytes"])
    t_world = time.perf_counter()
    log(f"[setup] world_s={t_world - t_jax:.3f}")

    calls0 = pr.train_calls
    pr.warm_capture = True
    warm = run_round(world, cfg, queries, net)
    pr.warm_capture = False
    warm_compile_s, warm_compiles = clock.mark()
    per_round = pr.train_call_sigs[calls0:]
    base = pr.train_calls
    pr.capture = {base + 1 + i for i in check.pick_train_calls(
        per_round, args.seed, work.forward_flops)}
    t_setup = time.perf_counter()
    setup_s = t_setup - T_START
    log(f"[setup] jax_start_s={t_jax - T_START:.3f} "
        f"world_s={t_world - t_jax:.3f} warm_round_s={warm['wall']:.3f} "
        f"warm_compile_s={warm_compile_s:.3f} "
        f"warm_backend_compiles={warm_compiles} setup_s={setup_s:.3f} "
        f"queries_per_round={len(queries)} "
        f"train_calls_per_round={len(per_round)}")

    rt = get_runtime()
    ds0 = rt.dispatch_stats()
    work0 = pr.counts()
    c0, n0 = clock.mark()
    trace_dir = None
    if args.trace:
        # deleted once read: a trace of a whole window is large
        trace_dir = TRACE_DIR / args.workload
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # the harness's own annotations and no runtime internals: a
        # window's trace at the default level is some 200 MB
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    pr.recording = True
    rounds = []
    with jax.profiler.TraceAnnotation("window"):
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < args.seconds:
            pr.round = len(rounds) + 1
            rounds.append(run_round(world, cfg, queries, net))
            log(f"[round] {len(rounds)} wall_s={rounds[-1]['wall']:.6f}")
        w1 = time.perf_counter()
    pr.recording = False
    if args.trace:
        jax.profiler.stop_trace()
    window_s = w1 - w0
    c1, n1 = clock.mark()
    ds1 = rt.dispatch_stats()
    work1 = pr.counts()
    mem = dev.memory_stats() or {}
    memory_peak = int(max((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0) for d in devices[:cell["chips"]]))

    qs = [q for r in rounds for q in r["queries"]]
    done = [q for q in qs if q["done"]]
    metrics = {
        "video_x": (sum(q["archive_s"] for q in done) / window_s, "x"),
        "first_s_p50": (statistics.median(q["first_s"] for q in qs), "s"),
        "final_s_p50": (statistics.median(q["final_s"] for q in qs), "s"),
        "setup_s": (setup_s, "s"),
    }
    log("[metrics] " + " ".join(f"{k}={v!r}" for k, (v, _u) in
                                 metrics.items()))
    dispatch = {k: ds1[k] - ds0[k] for k in ds0}
    sched = {}
    for r in rounds:
        for k, v in r["stats"].items():
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and k != "device_count":
                sched[k] = sched.get(k, 0) + v
    frames = {s: n - work0["score_frames"].get(s, 0)
              for s, n in work1["score_frames"].items()}
    frames = {s: n for s, n in frames.items() if n}
    samples = {s: n - work0["train_samples"].get(s, 0)
               for s, n in work1["train_samples"].items()}
    samples = {s: n for s, n in samples.items() if n}
    counters = {k: n - work0["extra"].get(k, 0)
                for k, n in work1["extra"].items()}
    log(f"[window] rounds={len(rounds)} window_s={window_s:.6f} "
        f"round_s={[round(r['wall'], 4) for r in rounds]} "
        f"compile_s={c1 - c0:.6f} backend_compiles={n1 - n0} "
        f"memory_peak_bytes={memory_peak} "
        f"bytes_in_use={mem.get('bytes_in_use')}")
    log(f"[window] dispatch_stats={dispatch}")
    log(f"[window] scheduler={sched}")
    log(f"[window] train_steps={work1['train_steps'] - work0['train_steps']}"
        f" train_calls={work1['train_calls'] - work0['train_calls']} "
        f"frames_by_sig={frames} train_samples_by_sig={samples} "
        f"counters={counters}")
    log("[window] queries=" + json.dumps(
        [[q["qid"], q["first_s"], q["final_s"]] for q in qs]))

    breakdown = None
    busy = None
    if args.trace:
        t0 = time.perf_counter()
        path = xtrace.find(str(trace_dir))
        size = Path(path).stat().st_size
        red = xtrace.reduce(path, probes_mod.SPAN_NAMES, KERNEL)
        shutil.rmtree(trace_dir, ignore_errors=True)
        prog = red["program"]
        ctx = dict(trace=red, program=prog, window_s=red["window_s"],
                   dispatch=dispatch, score_frames=frames,
                   train_samples=samples, counters=counters, peak=peak)
        metrics = {}
        for name, (entry, read) in load_readers(bench, args.workload).items():
            v = read(ctx)
            if v is not None:
                metrics[name] = (v, entry["unit"])
        busy = red
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"],
                     "program_idle_gaps": prog["idle_gaps"]}
        log(f"[trace] read_s={time.perf_counter() - t0:.3f} bytes={size} "
            f"window_s={red['window_s']:.6f} busy_s={red['busy_s']:.6f} "
            f"kernel_s={red['kernel_s']:.6f} devices={red['devices']} "
            f"self_s={red['self_s']}")
        log(f"[trace] idle_by_host={red['idle_by_host']}")
        log(f"[trace] lines={red['lines']}")
        log(f"[trace] device_ops={red['device_ops']}")
        log(f"[trace] idle_gaps={red['idle_gaps']}")
        log(f"[trace] module_s={red['module_s']}")
        for k in ("self_s", "counts", "total_s", "idle_by", "idle_gaps"):
            log(f"[trace] program_{k}={prog[k]}")

    # the check runs once the window has closed and peak memory is read
    t0 = time.perf_counter()
    world_params = {cam: traffic.scene_params(v.spec)
                    for cam, (v, _s, _c) in world.items()}
    checks, wrong = check.run(
        cfg, dets, limits, world_params, pr, rounds, args.seed,
        control=bool(args.control), scored=bool(frames),
        trained=bool(samples))
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    log(f"[check] seconds={time.perf_counter() - t0:.3f} "
        f"demands={len(pr.demands)} captures={len(pr.captures)} "
        f"answers={len(pr.answers)} wrong={wrong[:5]}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if busy is not None:
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
    result = {"correct": correct, "attempted": len(qs),
              "failed": len(qs) - len(done),
              "metrics": {k: {"value": v, "unit": u}
                          for k, (v, u) in metrics.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
