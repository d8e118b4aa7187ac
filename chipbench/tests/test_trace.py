"""``xtrace.py`` on hand-made spans and on traces that ``record_trace.py``
recorded (committed under ``data/``): ``trace_cpu.xplane.pb`` on a CPU,
which holds the host spans but no device plane, and
``trace_chip.xplane.pb`` on a TPU v5e (64 crops scored, three Adam
steps, a 50 ms sleep)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

import xtrace  # noqa: E402

CPU_TRACE = HERE / "data" / "trace_cpu.xplane.pb"
CHIP_TRACE = HERE / "data" / "trace_chip.xplane.pb"
SPANS = ("submit", "run", "train", "render", "score", "verify")


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_times_subtract_children():
    spans = [(0, 100, "run"), (10, 40, "train"), (20, 30, "render"),
             (50, 60, "score"), (55, 58, "render")]
    selft, pieces = xtrace._self_times(spans)
    assert selft == {"run": 60, "train": 20, "render": 13, "score": 7}
    assert sum(b - a for a, b, _ in pieces) == 100


class _Event:
    def __init__(self, name):
        self.name = name


def test_kernel_matched_by_instruction_name():
    kernel = _Event("%conv_scorer.2 = f32[13,13,8,512]{3,2,1,0} "
                    "custom-call(f32[14,14,12,512]{3,2,1,0} %pad)")
    reader = _Event("%copy = f32[13,13,8,512]{3,2,1,0} "
                    "copy(f32[13,13,8,512]{3,2,1,0} %conv_scorer.2)")
    assert xtrace.op_name(kernel.name) == "%conv_scorer.2"
    assert xtrace._is_kernel(kernel, "conv_scorer")
    assert not xtrace._is_kernel(reader, "conv_scorer")


def test_cpu_trace_host_spans():
    r = xtrace.reduce(str(CPU_TRACE), SPANS, "conv_scorer")
    selft = r["self_s"]
    assert {"run", "train", "score"} == set(selft)
    # the recorder sleeps 50 ms inside "run" and nowhere else
    assert selft["run"] >= 0.045
    assert sum(selft.values()) <= r["window_s"] + 1e-9
    assert r["devices"] == 0 and r["busy_s"] == 0.0


@pytest.fixture(scope="module")
def reduced():
    if not CHIP_TRACE.exists():
        pytest.skip("no trace recorded on a TPU is committed yet")
    return xtrace.reduce(str(CHIP_TRACE), SPANS, "conv_scorer")


def test_chip_trace_busy_and_idle(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the recorder sleeps 50 ms inside "run" with nothing on the device
    gap, seconds = reduced["idle_gaps"][0]
    assert gap == "loop" and seconds >= 0.045
    assert sum(s for _, s in reduced["idle_gaps"]) <= \
        reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_chip_trace_kernel_and_spans(reduced):
    assert 0 < reduced["kernel_s"] <= reduced["busy_s"]
    selft = reduced["self_s"]
    assert {"run", "train", "score"} <= set(selft)
    assert selft["run"] >= 0.045
    assert sum(selft.values()) <= reduced["window_s"] + 1e-9
    names = [name for name, _ in reduced["device_ops"]]
    assert any("conv_scorer" in n for n in names), names
