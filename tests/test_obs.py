"""The program's ``diva.`` spans, read back from a ``jax.profiler``
trace taken the way the chip benchmark takes one (host tracer level 1,
no Python tracer): each span sits where its work happens, once per
unit of that work, and tracing changes no result."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.hardware import CloudModel
from repro.core.operators import OperatorArch, train_operator
from repro.core.runtime import OperatorRuntime, ScoreBatcher
from repro.core.training import CloudTrainer, FrameBank, TrainedOp
from repro.core.video import Video, corpus

ARCH = OperatorArch("obs_L2c8d16s25", 2, 8, 16, 25)


class Trace:
    """The ``diva.`` spans of one trace: (start_ns, end_ns, name, thread)."""

    def __init__(self, spans):
        self.spans = sorted(spans)

    def named(self, name):
        return [s for s in self.spans if s[2] == name]

    def inside(self, outer, name):
        """Spans called ``name`` that ``outer`` holds, on its thread."""
        a, b, _, line = outer
        return [s for s in self.named(name)
                if s[3] == line and a <= s[0] and s[1] <= b]


def traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; returns (its result, Trace)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(ev.start_ns, ev.end_ns, ev.name, line.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("diva.")]
    return out, Trace(spans)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    crops = rng.uniform(size=(64, 25, 25, 3)).astype(np.float32)
    labels = (np.arange(64) % 2).astype(np.float32)
    return crops, labels


@pytest.fixture(scope="module")
def bank():
    return FrameBank(Video(corpus(hours=0.02)["Banff"]))   # 72 frames


def _train(data):
    crops, labels = data
    return train_operator(ARCH, None, crops, labels, labels, steps=3,
                          batch=32, seed=5)


def test_span_names_are_namespaced():
    names = [v for k, v in vars(obs).items() if k.isupper()]
    assert len(names) == 16 and len(set(names)) == 16
    assert all(n.startswith("diva.") for n in names)


def test_train_operator_spans(tmp_path, data):
    _train(data)                       # compile outside the trace
    _, tr = traced(tmp_path, lambda: _train(data))
    steps = tr.named(obs.TRAIN_STEP)
    assert len(steps) == 3
    for step in steps:
        assert len(tr.inside(step, obs.TRAIN_GATHER)) == 1
        assert len(tr.inside(step, obs.TRAIN_DISPATCH)) == 1
    assert len(tr.named(obs.TRAIN_GATHER)) == 3
    assert len(tr.named(obs.TRAIN_DISPATCH)) == 3
    assert len(tr.named(obs.TRAIN_INIT)) == 1
    assert len(tr.named(obs.TRAIN_UPLOAD)) == 1


def test_trained_params_identical_under_trace(tmp_path, data):
    plain = _train(data)
    under, _ = traced(tmp_path, lambda: _train(data))
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(under)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cloud_trainer_spans(tmp_path, bank):
    video = bank.video
    idxs = np.arange(video.spec.num_frames)
    trainer = CloudTrainer(bank, "car", CloudModel(), train_steps=3)
    trainer.add_samples(idxs, video.gt_present_vec(idxs, "car"),
                        video.gt_count_vec(idxs, "car"))
    _, tr = traced(tmp_path, lambda: trainer.train(ARCH).params)
    call, = tr.named(obs.TRAIN)
    assert len(tr.inside(call, obs.TRAIN_VALIDATE)) == 1
    # the training set's crops and the validation split's
    assert len(tr.inside(call, obs.FRAMES_CROP)) == 2
    assert tr.inside(call, obs.FRAMES_RENDER)
    assert len(tr.inside(call, obs.TRAIN_STEP)) == 3
    assert len(tr.inside(call, obs.TRAIN_INIT)) == 1


def _score_crops(rt, params, crops):
    return rt.score_crops(params, ARCH, crops)


def _superbatch(rt, params, bank):
    """Two demands of one signature in one (2, bucket) superbatch."""
    batcher = ScoreBatcher(rt, group_max=2)
    trained = TrainedOp(ARCH, params, 0, 0.5, (0.0, 1.0), 0.0, 1.0)
    handles = [batcher.submit(trained, bank, np.arange(a, a + 20))
               for a in (0, 30)]
    batcher.flush()
    return [h.result() for h in handles]


@pytest.mark.parametrize("case,dispatches,stacks", [
    ("score_crops", 2, 0),     # 64 crops in chunks of 32
    ("superbatch", 1, 1),      # two demands fill a group of two
])
def test_scoring_spans(tmp_path, data, bank, case, dispatches, stacks):
    crops, labels = data
    params = train_operator(ARCH, None, crops, labels, labels, steps=1,
                            batch=32)
    rt = OperatorRuntime(small_flops=0.0, chunk=32)
    if case == "score_crops":
        def run():
            return _score_crops(rt, params, crops)
    else:
        def run():
            return _superbatch(rt, params, bank)
    run()                              # compile outside the trace
    before = rt.dispatch_stats()
    _, tr = traced(tmp_path, run)
    calls = rt.dispatch_stats()["calls"] - before["calls"]
    assert calls == dispatches
    assert len(tr.named(obs.SCORE_DISPATCH)) == calls
    # one conversion of each dispatch's results to the host
    assert len(tr.named(obs.SCORE_WAIT)) == calls
    assert len(tr.named(obs.SCORE_STACK)) == stacks
    if case == "superbatch":
        submits = tr.named(obs.SCORE_SUBMIT)
        assert len(submits) == 2
        # the second demand fills the group: its submit dispatches it
        assert len(tr.inside(submits[1], obs.SCORE_STACK)) == 1
        assert len(tr.inside(submits[1], obs.SCORE_DISPATCH)) == 1


def test_fleet_and_oracle_spans(tmp_path, bank):
    from repro.core import landmarks as lm
    from repro.core.hardware import YOLO_V3
    from repro.core.query import Query
    from repro.serving.fleet import FleetService

    video = bank.video
    svc = FleetService()
    svc.register_camera("Banff", video, lm.build_landmarks(video, 30,
                                                           YOLO_V3))

    def serve():
        svc.submit("Banff", Query("count_avg", "car"))
        return svc.run()

    _, tr = traced(tmp_path, serve)
    assert len(tr.named(obs.FLEET_SUBMIT)) == 1
    run, = tr.named(obs.FLEET_RUN)
    slots = svc.scheduler.stats["oracle"]["slots"]
    assert slots > 0
    # every slot is one OracleService.step, inside the scheduler's loop
    assert len(tr.inside(run, obs.VERIFY)) >= slots
