"""The program's own spans as ``xtrace.reduce`` keeps them
(``summarize``) and the four per-layer metrics that read them
(``layers/``), on hand-made spans and on the committed traces, which
were recorded before the program had spans of its own: there the
program's tables are empty and every other key of ``xtrace.reduce``
reads what it read when they were recorded."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent)]

import traffic  # noqa: E402
import xtrace  # noqa: E402

READINGS = ("adam_step_host_ms", "train_setup_ms", "crop_share",
            "score_dispatch_ms")

TRACES = {"cpu": HERE / "data" / "trace_cpu.xplane.pb",
          "chip": HERE / "data" / "trace_chip.xplane.pb"}
SPANS = ("submit", "run", "train", "render", "score", "verify")

# xtrace.reduce on the committed traces, as recorded with its code when
# the program had no spans: device_ops by instruction name
PINNED = {
    "cpu": {
        "window_s": 0.07941424500000001, "busy_s": 0.0, "devices": 0,
        "kernel_s": 0.0,
        "self_s": {"score": 0.014343168000000002, "train": 0.014855095,
                   "run": 0.050201674},
        "device_ops": [], "idle_gaps": [], "idle_by_host": {},
    },
    "chip": {
        "window_s": 0.101631207, "busy_s": 0.000151587, "devices": 1,
        "kernel_s": 2.877e-05,
        "self_s": {"score": 0.00773682, "train": 0.042925889,
                   "run": 0.050964088000000005},
        "device_ops": [
            ["conv_scorer", 2.877e-05], ["%copy", 1.3798e-05],
            ["%copy.1", 1.3175e-05], ["%copy.1", 1.2422e-05],
            ["%copy.1", 8.370000000000001e-06],
            ["%multiply_clamp_fusion", 7.141000000000001e-06],
            ["%copy", 5.116000000000001e-06],
            ["%divide_subtract_fusion.2", 5.006e-06],
            ["%select_n.1", 4.918e-06], ["%fusion", 3.888e-06]],
        "idle_gaps": [
            ["loop", 0.052869739000000006], ["score", 0.004734753],
            ["score", 0.00393411], ["train", 0.001792785],
            ["train", 0.00172021], ["train", 0.001711384],
            ["train", 0.00165113], ["train", 0.001217624],
            ["train", 0.001144548], ["train", 0.0009314970000000001]],
        "idle_by_host": {"loop": 0.052869739000000006,
                         "score": 0.008668934000000003,
                         "train": 0.03994094699999974},
    },
}


def readings(p: dict, window_s: float) -> dict:
    """The four metrics, each read by its own ``layers/`` file."""
    ctx = {"program": p, "window_s": window_s}
    return {name: traffic.load_module(HERE.parent / "layers" / f"{name}.py",
                                      f"test_layer_{name}").read(ctx)
            for name in READINGS}


def test_summarize_hand_made_spans():
    spans = [(0, 100, "diva.fleet.run", "main"),
             (10, 40, "diva.train", "main"),
             (20, 30, "diva.train.step", "main"),
             (22, 28, "diva.train.dispatch", "main"),
             (32, 38, "diva.train.step", "main"),
             (50, 60, "diva.score.dispatch", "worker"),
             (-10, 5, "diva.frames.crop", "main")]      # clipped at 0
    p = xtrace.summarize(spans, (0, 120),
                                [(24, 26), (60, 70), (105, 110)])
    ns = 1e-9
    assert p["counts"] == {"diva.fleet.run": 1, "diva.train": 1,
                           "diva.train.step": 2, "diva.train.dispatch": 1,
                           "diva.score.dispatch": 1, "diva.frames.crop": 1}
    assert p["self_s"] == pytest.approx({
        "diva.fleet.run": 65 * ns, "diva.train": 14 * ns,
        "diva.train.step": 10 * ns, "diva.train.dispatch": 6 * ns,
        "diva.score.dispatch": 10 * ns, "diva.frames.crop": 5 * ns})
    assert p["total_s"]["diva.train.step"] == pytest.approx(16 * ns)
    assert p["total_s"]["diva.frames.crop"] == pytest.approx(5 * ns)
    # each gap goes to the innermost span at its midpoint: 12 (diva.train
    # between crop and step), 43 and 87.5 (the loop), 115 (none)
    labels = sorted((label, round(s / ns)) for label, s in p["idle_gaps"])
    assert labels == [("diva.fleet.run", 34), ("diva.fleet.run", 35),
                      ("diva.train", 24), ("none", 10)]
    assert p["idle_by"] == pytest.approx({"diva.fleet.run": 69 * ns,
                                          "diva.train": 24 * ns,
                                          "none": 10 * ns})
    r = readings(p, 120 * ns)
    assert r["adam_step_host_ms"] == pytest.approx(8e-6)
    assert r["train_setup_ms"] == pytest.approx(14e-6)
    assert r["crop_share"] == pytest.approx(100 * 5 / 120)
    assert r["score_dispatch_ms"] == pytest.approx(10e-6)


def test_readings_missing_spans_are_none():
    p = xtrace.summarize([], (0, 10), [])
    assert p["idle_gaps"] == [["none", 10e-9]]
    assert set(readings(p, 1.0).values()) == {None}


@pytest.mark.parametrize("trace", sorted(TRACES))
def test_committed_traces_have_no_program_spans(trace):
    p = xtrace.reduce(str(TRACES[trace]), SPANS, "conv_scorer")["program"]
    assert p["self_s"] == p["counts"] == p["total_s"] == {}
    assert set(p["idle_by"]) <= {"none"}


@pytest.mark.parametrize("trace", sorted(TRACES))
def test_harness_reduce_reads_as_recorded(trace):
    r = xtrace.reduce(str(TRACES[trace]), SPANS, "conv_scorer")
    for key in ("lines", "program", "module_s"):   # test_trace.py's
        r.pop(key)
    r["device_ops"] = [[xtrace.op_name(k), v] for k, v in r["device_ops"]]
    assert r == PINNED[trace]


def test_tiny_cell_counts_match_the_harness(tmp_path):
    """On the tiny CPU cell, traced, the program's spans count the Adam
    steps and the scoring dispatches that the harness counts in the
    window, and the four metrics are reported."""
    import ast

    import tiny

    result, out, _err = tiny.run(tiny.make_copy(tmp_path), "--trace", "1")

    def logged(key):
        line, = [ln for ln in out.splitlines() if f" {key}=" in ln]
        return line.split(f" {key}=", 1)[1]

    counts = ast.literal_eval(logged("program_counts"))
    steps = int(logged("train_steps").split()[0])
    calls = ast.literal_eval(logged("dispatch_stats"))["calls"]
    assert steps > 0 and counts["diva.train.step"] == steps
    assert calls > 0 and counts["diva.score.dispatch"] == calls
    for name in READINGS:
        assert result["metrics"][name]["value"] > 0, name
    assert len(result["breakdown"]["program_idle_gaps"]) <= 10
    assert isinstance(ast.literal_eval(logged("module_s")), dict)
    assert result["correct"] is True
