"""Device-parallel scoring: sharded-vs-unsharded bitwise equivalence on
a forced multi-device CPU host, plus pure (device-free) unit tests for
the scoring-batch sharding specs and their divisibility fallbacks.

The forced device count (``XLA_FLAGS=--xla_force_host_platform_device_
count=4``) must be set before jax first initializes, so the equivalence
check runs in a subprocess (``tests/_sharded_subprocess.py``) — which
also makes it valid under the plain tier-1 suite, not only the CI
multi-device job.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

from _hypothesis_compat import given, st
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel import sharding

ROOT = Path(__file__).resolve().parent.parent


DATA4 = AbstractMesh((4,), ("data",))


# -- spec unit tests (no devices needed) -------------------------------------


def test_frames_spec_shards_divisible_dim0():
    fb = []
    assert sharding.frames_spec((64, 25, 25, 3), DATA4, fb) == \
        P("data", None, None, None)
    assert fb == []


def test_frames_spec_fallback_replicates():
    fb = []
    assert sharding.frames_spec((63, 25, 25, 3), DATA4, fb) == \
        P(None, None, None, None)
    assert fb == [("frames", 63, ("data",))]


def test_superbatch_spec_prefers_group_axis():
    fb = []
    assert sharding.superbatch_spec((8, 256, 50, 50, 3), DATA4, fb) == \
        P("data", None, None, None, None)
    assert fb == []


def test_superbatch_spec_group_fallback_replicates():
    """A group size that does not divide the data axis replicates —
    recorded, not fatal, and deliberately NOT retried on the frames
    axis (frame-axis partitioning is not bitwise-safe on XLA:CPU, so
    the superbatch path never takes it implicitly)."""
    fb = []
    assert sharding.superbatch_spec((3, 256, 50, 50, 3), DATA4, fb) == \
        P(None, None, None, None, None)
    assert fb == [("group", 3, ("data",))]


@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=2048))
def test_superbatch_spec_property(group, frames):
    """Group-sharded iff the group divides the mesh, else fully
    replicated — regardless of the frame count; never errors."""
    spec = sharding.superbatch_spec((group, frames, 25, 25, 3), DATA4)
    if group % 4 == 0:
        assert spec[0] == "data" and spec[1] is None
    else:
        assert spec == P(None, None, None, None, None)


def test_explain_fallbacks_summarizes():
    fb = [("group", 3, ("data",)), ("group", 3, ("data",)),
          ("group", 5, ("data",)), ("frames", 255, ("data",)),
          ("vocab", 30, ("model",))]
    out = sharding.explain_fallbacks(fb)
    assert {e["axis"]: e for e in out}["group"] == \
        {"axis": "group", "mesh_axes": ["data"], "count": 3, "dims": [3, 5]}
    assert {e["axis"] for e in out} == {"group", "frames", "vocab"}
    assert sharding.explain_fallbacks([]) == []


def test_spec_for_leaf_replication_paths():
    """The primitive all scoring specs build on: unmapped axes, unknown
    rules, and non-dividing dims all replicate; only the mapped,
    dividing dim shards — and only real step-downs are recorded."""
    rules = {"frames": ("data",)}
    fb = []
    # unmapped (None) axis: replicated, NOT a fallback record
    assert sharding.spec_for_leaf((64, 25), (None, None), DATA4,
                                  rules, fb) == P(None, None)
    assert fb == []
    # axis missing from the rules: replicated, not recorded
    assert sharding.spec_for_leaf((64, 25), ("mystery", None), DATA4,
                                  rules, fb) == P(None, None)
    assert fb == []
    # mapped but non-dividing: replicated AND recorded
    assert sharding.spec_for_leaf((63, 25), ("frames", None), DATA4,
                                  rules, fb) == P(None, None)
    assert fb == [("frames", 63, ("data",))]
    # mapped and dividing: sharded
    assert sharding.spec_for_leaf((64, 25), ("frames", None), DATA4,
                                  rules) == P("data", None)


# -- forced multi-device equivalence (subprocess) ----------------------------


def test_sharded_fleet_bitwise_equivalent_on_forced_devices():
    """Acceptance: with ``--xla_force_host_platform_device_count=4``,
    mesh-sharded fleet scoring is bitwise Progress-equivalent to the
    single-device path, traces once per (signature, shape) (TraceGuard
    passes in the worker), and per-arch trace counts match the
    unsharded run — no per-shard retraces."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT / "tests")] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_sharded_subprocess.py")],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, \
        f"sharded equivalence worker failed:\n{proc.stdout}\n{proc.stderr}"
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["device_count"] == 4
    assert report["mesh_shape"] == {"data": 4}
    assert report["fleet_traces_per_arch"]
    assert report["super_calls"] > 0          # superbatches ran sharded
    # the non-dividing probe group exercised the frames-axis fallback
    assert any(e["axis"] == "group" for e in report["sharding_fallbacks"])
