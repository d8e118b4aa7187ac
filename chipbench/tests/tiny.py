"""A copy of the benchmark with one tiny CPU cell, and a way to run it.

``make_copy(dst)`` copies ``BENCHMARK.json`` and ``chipbench/`` into
``dst``, links the program's ``src/``, and adds configuration ``tiny``
(diva3 at 0.02 h per camera, 10 train steps, the small operator
family) with cell ``tiny.single`` under diva3.tagging's limits.
``run(dst, ...)`` runs the harness there in a child process on the
CPU, past its look for a chip, with an optional fault from
``faults.py`` planted in the program first, and returns the result
line and the child's output. Past the look for a chip means the child
replaces ``run.device_check`` with one that takes the CPU and the
v5e's peaks.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.single"


def make_copy(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(ROOT / "src", dst / "src")
    cfg = json.loads((ROOT / "chipbench/configs/diva3.json").read_text())
    cfg.update(name="tiny", hours=0.02, train_steps=10, full_family=False)
    (dst / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    # the tighter cell's limits, which the control and faults must fail
    shutil.copy(ROOT / "chipbench/limits/diva3.tagging.json",
                dst / f"chipbench/limits/{CELL}.json")
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "single", "chips": 1,
                               "why": "tiny CPU cell for the tests"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(CELL)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run(dst: Path, *args: str, fault: str = "", cell: str = CELL,
        seconds: str = "2", timeout: int = 600):
    argv = ["--workload", cell, "--seed", "3000000001", "--seconds", seconds,
            *args]
    code = (
        "import sys\n"
        f"sys.path[:0] = [{str(dst / 'chipbench')!r}, "
        f"{str(Path(__file__).parent)!r}, {str(dst / 'src')!r}]\n"
        "import faults, run, work\n"
        f"faults.plant({fault!r})\n"
        "run.device_check = lambda jax, cell: "
        "(jax.devices(), work.peaks('TPU v5 lite'))\n"
        f"run.main({argv!r})\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(dst / ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=dst, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    last = proc.stdout.strip().splitlines()[-1]
    return json.loads(last), proc.stdout, proc.stderr
