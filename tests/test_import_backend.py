"""A chip belongs to one process at a time, so importing the program
must leave JAX's backends alone: a parent that only imports (the
benchmark runner, a server's launcher) can then start children that
take the chip. Each import runs in a fresh process, the way an entry
point starts, and reports whether a backend was initialised."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

EVERY_SRC_MODULE = (
    "import importlib, pkgutil, repro\n"
    "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
    "    importlib.import_module(m.name)\n")


def _backend_initialised_after(code: str) -> bool:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    probe = code + ("\nimport jax._src.xla_bridge as xb\n"
                    "print('initialised', xb.backends_are_initialized())\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1] == "initialised True"


@pytest.mark.parametrize("module", [
    "repro.core.fleet", "repro.serving.fleet", "benchmarks.run",
    "benchmarks.bench_fleet", "chip_smoke"])
def test_entry_point_import_leaves_backend_alone(module):
    assert not _backend_initialised_after(f"import {module}")


def test_no_src_module_initialises_backend_at_import():
    assert not _backend_initialised_after(EVERY_SRC_MODULE)


def test_import_guard_detects_initialisation():
    """The probe itself: touching a device does initialise."""
    assert _backend_initialised_after("import jax; jax.devices()")
