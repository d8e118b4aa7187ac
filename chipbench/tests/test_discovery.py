"""A configuration, a traffic mix, a cell's limits, a per-layer metric or
a detector reference is a new file; the harness finds it by the name in
``BENCHMARK.json`` or in the configuration, with no edit to its code.
The tiny copy adds a configuration (``tiny``) and a cell with its
limits; here a mix, its cell's limits and a per-layer reader are
dropped in as well, or a configuration whose cloud detector is answered
for by a reference module of its own (``simtier.py``)."""
from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import pytest

import tiny

HERE = Path(__file__).resolve().parent


def _add(copy, workload=None, per_layer=None):
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    if workload:
        bench["workloads"].append(workload)
    if per_layer:
        bench["per_layer"].append(per_layer)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_picked_up(tmp_path):
    copy = tiny.make_copy(tmp_path)
    (copy / "chipbench/mixes/tiny_pair.json").write_text(json.dumps({
        "queries": [{"camera": "JacksonH", "kind": "count_avg"},
                    {"camera": "Banff", "kind": "count_avg"}]}))
    (copy / "chipbench/limits/tiny.pair.json").write_text(json.dumps({
        "answers_wrong": 0}))
    (copy / "chipbench/layers/rounds_seen.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    _add(copy, {"name": "tiny.pair", "config": "tiny",
                "traffic": "tiny_pair", "chips": 1,
                "why": "a dropped-in mix"},
         {"name": "rounds_seen", "unit": "n", "better": "higher",
          "source": "program_counter", "layer": "scheduler and batcher",
          "moves": "video_x", "workloads": ["tiny.pair"]})
    result, out, _err = tiny.run(copy, "--trace", "1", cell="tiny.pair")
    assert result["metrics"]["rounds_seen"]["value"] == 42.0
    assert "queries_per_round=2" in out
    assert result["correct"] is True


@pytest.mark.parametrize("flip", ["", "unsure", "sure"])
def test_detector_reference_by_file(tmp_path, flip):
    """The cloud detector is YOLOv2, answered for by ``simtier``: the
    program must verify with YOLOv2 to come out correct, the module's
    counter reaches a reader and its number the checks. Its answer
    flipped on a frame it is not sure of is not held against the
    program; flipped on a frame it is sure of, it is."""
    copy = tiny.make_copy(tmp_path)
    cfg = json.loads((copy / "chipbench/configs/tiny.json").read_text())
    cfg.update(name="tiny_v2", cloud_detector="yolov2")
    cfg["detectors"]["yolov2"] = {"accuracy": 0.82, "reference": "simtier"}
    (copy / "chipbench/configs/tiny_v2.json").write_text(json.dumps(cfg))
    (copy / "chipbench/detectors").mkdir()
    src = (HERE / "simtier.py").read_text()
    (copy / "chipbench/detectors/simtier.py").write_text(
        src.replace('FLIP = ""', f"FLIP = {flip!r}"))
    limits = json.loads((copy / f"chipbench/limits/{tiny.CELL}.json")
                        .read_text())
    (copy / "chipbench/limits/tiny_v2.single.json").write_text(json.dumps(
        dict(limits, simtier_capture_wrong=0)))
    (copy / "chipbench/layers/simtier_answers.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx['counters'].get('simtier_answers', 0))\n")
    _add(copy, {"name": "tiny_v2.single", "config": "tiny_v2",
                "traffic": "single", "chips": 1,
                "why": "a dropped-in detector reference"},
         {"name": "simtier_answers", "unit": "n", "better": "higher",
          "source": "program_counter", "layer": "oracle",
          "moves": "final_s_p50", "workloads": ["tiny_v2.single"]})
    result, out, _err = tiny.run(copy, "--trace", "1" if not flip else "0",
                                 cell="tiny_v2.single")
    checks = result["checks"]
    answers = int(re.search(r" answers=(\d+)", out).group(1))
    assert answers > 0
    if not flip:
        assert result["metrics"]["simtier_answers"]["value"] == answers
        assert checks["simtier_capture_wrong"] == {"value": 0, "limit": 0}
    rounds = int(re.search(r"\[window\] rounds=(\d+)", out).group(1))
    if flip == "sure":
        assert checks["answers_wrong"]["value"] == rounds
        assert result["correct"] is False
    else:
        assert checks["answers_wrong"]["value"] == 0
        assert result["correct"] is True, checks
