"""A configuration, a traffic mix, a cell's limits or a per-layer
metric is a new file; the harness finds it by the name in
``BENCHMARK.json``, with no edit to its code. The tiny copy adds a
configuration (``tiny``) and a cell with its limits; here a mix, its
cell's limits and a per-layer reader are dropped in as well."""
from __future__ import annotations

import json

import tiny


def test_new_files_are_picked_up(tmp_path):
    copy = tiny.make_copy(tmp_path)
    (copy / "chipbench/mixes/tiny_pair.json").write_text(json.dumps({
        "queries": [{"camera": "JacksonH", "kind": "count_avg"},
                    {"camera": "Banff", "kind": "count_avg"}]}))
    (copy / "chipbench/limits/tiny.pair.json").write_text(json.dumps({
        "answers_wrong": 0}))
    (copy / "chipbench/layers/rounds_seen.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.pair", "config": "tiny",
                               "traffic": "tiny_pair", "chips": 1,
                               "why": "a dropped-in mix"})
    bench["per_layer"].append({
        "name": "rounds_seen", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "scheduler and batcher",
        "moves": "video_x", "workloads": ["tiny.pair"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    result, out, _err = tiny.run(copy, "--trace", "1", cell="tiny.pair")
    assert result["metrics"]["rounds_seen"]["value"] == 42.0
    assert "queries_per_round=2" in out
    assert result["correct"] is True
