"""Benchmark driver: one module per paper figure + the roofline reader.

  PYTHONPATH=src python -m benchmarks.run                 # standard profile
  PYTHONPATH=src python -m benchmarks.run --profile quick
  PYTHONPATH=src python -m benchmarks.run --figures fig9,roofline

Each figure runs in a child process of its own, one after another, and
this parent never touches JAX: a chip belongs to one process at a time,
and a figure (``fleet``) may start processes of its own that need it.

Outputs: printed tables (tee to bench_output.txt) + results/bench/*.csv.
The multi-pod dry-run itself is not re-run here (it takes ~45 min of
XLA compiles); run `python -m repro.launch.dryrun` to regenerate its
artifacts — `roofline` reads them."""
from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# figure name -> module under benchmarks/ whose main(profile) runs it
FIGURES = {
    "fig6": "fig6_operators",
    "fig9": "fig9_queries",
    "fig10": "fig10_counting",
    "fig11": "fig11_traffic",
    "fig12": "fig12_ablation",
    "fig13": "fig13_landmarks",
    "roofline": "roofline",
    "operator_runtime": "bench_runtime",
    "fleet": "bench_fleet",
    "oracle": "bench_oracle",
}


def child_env() -> dict:
    """The environment for a benchmark child: this checkout's ``src``
    and root on ``PYTHONPATH``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def run_figure(name: str, profile: str) -> None:
    """Run one figure in this process (the child's side)."""
    from repro.launch import compile_cache
    compile_cache.enable()
    importlib.import_module(f"benchmarks.{FIGURES[name]}").main(profile)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="standard",
                    choices=["quick", "standard", "paper"])
    ap.add_argument("--figures", default="all",
                    help="comma list of: " + ",".join(FIGURES))
    args = ap.parse_args()

    names = list(FIGURES) if args.figures == "all" else \
        [f.strip() for f in args.figures.split(",")]
    unknown = [n for n in names if n not in FIGURES]
    if unknown:
        ap.error(f"unknown figures: {', '.join(unknown)}")
    t0 = time.time()
    failures = []
    for name in names:
        print(f"\n######## {name} (profile={args.profile}) ########",
              flush=True)
        code = ("from benchmarks.run import run_figure; "
                f"run_figure({name!r}, {args.profile!r})")
        rc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                            env=child_env()).returncode
        if rc != 0:   # run the rest, report at end
            failures.append((name, f"exit code {rc}"))
    print(f"\nbenchmarks done in {time.time() - t0:.0f}s; "
          f"{len(names) - len(failures)}/{len(names)} figures ok")
    for name, err in failures:
        print(f"  FAILED {name}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
