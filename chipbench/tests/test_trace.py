"""``xtrace.py`` on hand-made spans and on traces that ``record_trace.py``
recorded (committed under ``data/``): ``trace_cpu.xplane.pb`` on a CPU,
which holds the host spans but no device plane, and
``trace_chip.xplane.pb`` on a TPU v5e (64 crops scored, three Adam
steps, a 50 ms sleep). Both were recorded before the program opened
spans of its own, so their ``program`` tables are empty."""
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


def test_module_name_drops_the_hash():
    assert xtrace.module_name("jit_scorer(3975087741005274656)") == \
        "jit_scorer"
    assert xtrace.module_name("jit__adam_step_body(17)") == \
        "jit__adam_step_body"
    assert xtrace.module_name("jit_f") == "jit_f"


# device seconds per jitted program on the committed chip trace, from its
# XLA Modules line (one device)
CHIP_MODULE_S = {
    "jit_scorer": 4.3577e-05, "jit_convert_element_type": 9.505e-06,
    "jit_broadcast_in_dim": 2.2019e-05, "jit_add": 5.328e-06,
    "jit_select_n": 7.746e-06, "jit__broadcast_arrays": 1.3249e-05,
    "jit_gather": 3.0551e-05, "jit__adam_step_body": 4.4822e-05}

# the chip trace's idle gaps, as the harness's own reduction has them
# ("idle_gaps" of PINNED in test_program_spans.py), under no program span
CHIP_GAPS_S = [0.052869739, 0.004734753, 0.00393411, 0.001792785,
               0.00172021, 0.001711384, 0.00165113, 0.001217624,
               0.001144548, 0.000931497]


def test_module_s_on_committed_traces(reduced):
    assert xtrace.reduce(str(CPU_TRACE), SPANS, "conv_scorer")[
        "module_s"] == {}
    assert reduced["module_s"] == pytest.approx(CHIP_MODULE_S)
    # a program's time holds its operations' time
    assert reduced["module_s"]["jit_scorer"] >= reduced["kernel_s"]


def test_program_on_committed_traces(reduced):
    cpu = xtrace.reduce(str(CPU_TRACE), SPANS, "conv_scorer")
    assert cpu["program"] == {
        "self_s": {}, "counts": {}, "total_s": {},
        "idle_by": {"none": pytest.approx(cpu["window_s"])},
        "idle_gaps": [["none", pytest.approx(cpu["window_s"])]]}
    p = reduced["program"]
    assert p["self_s"] == p["counts"] == p["total_s"] == {}
    assert [label for label, _ in p["idle_gaps"]] == ["none"] * 10
    assert [s for _, s in p["idle_gaps"]] == pytest.approx(CHIP_GAPS_S)
    assert p["idle_by"] == {"none": pytest.approx(
        reduced["window_s"] - reduced["busy_s"])}


def test_reduce_keeps_program_spans(tmp_path, monkeypatch):
    """Program spans on the host plane reach ``program``, apart from the
    harness's spans, and device modules reach ``module_s``."""
    from types import SimpleNamespace as NS

    def ev(name, a, b):
        return NS(name=name, start_ns=a, end_ns=b, duration_ns=b - a)

    host = NS(name=xtrace.HOST_PLANE, lines=[NS(name="main", events=[
        ev("window", 0, 100), ev("run", 0, 100), ev("diva.fleet.run", 5, 95),
        ev("diva.train.step", 10, 30), ev("diva.train.dispatch", 20, 30)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(1)", 20, 40),
                                       ev("jit_a(2)", 60, 70),
                                       ev("jit_b(3)", 90, 120)]),
        NS(name="XLA Ops", events=[ev("%x = f32[] add()", 25, 35)])])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda _p: NS(planes=[host, dev])))
    r = xtrace.reduce("any", SPANS, "conv_scorer")
    assert r["self_s"] == {"run": pytest.approx(100e-9)}
    p = r["program"]
    assert p["counts"] == {"diva.fleet.run": 1, "diva.train.step": 1,
                           "diva.train.dispatch": 1}
    assert p["self_s"] == pytest.approx({"diva.fleet.run": 70e-9,
                                         "diva.train.step": 10e-9,
                                         "diva.train.dispatch": 10e-9})
    # gaps (0, 25) and (35, 100) around the one op, by their midpoints
    assert p["idle_by"] == pytest.approx({"diva.train.step": 25e-9,
                                          "diva.fleet.run": 65e-9})
    assert r["module_s"] == pytest.approx({"jit_a": 30e-9, "jit_b": 10e-9})
