"""What the harness observes of the program, from its own files.

``install`` wraps public calls into each layer:

  * a ``jax.profiler.TraceAnnotation`` span (a no-op unless a trace is
    running) named for the layer: ``submit`` (``FleetService.submit``),
    ``run`` (``FleetService.run``, whose self time is the scheduler's
    own loop), ``train`` (``CloudTrainer.train``), ``render``
    (``Video.render_frames``), ``score`` (``ScoreBatcher``,
    ``ScoreHandle.result``, ``OperatorRuntime.score_crops``) and
    ``verify`` (``OracleService``);
  * counters: real frames scored per operator signature, and training
    samples per signature over every Adam step;
  * while ``recording`` is on (the measured window), what the
    correctness check needs: every score demand with its result handle,
    every verification answer in order, and, of the fresh training calls
    (a new operator) named in ``capture``: the frames of the training
    set, the trainer and how many answers it had seen, and the first
    ``STEPS`` Adam steps (minibatch, augmentation, parameters after the
    step, and the first moment after the first step).

The same wrappers are in place with and without ``--trace``, so the
timed path is the same in both.
"""
from __future__ import annotations

import collections
import functools
import inspect
from typing import Dict, List, Set, Tuple

import numpy as np

SPAN_NAMES = ("submit", "run", "train", "render", "score", "verify")
STEPS = 3                # Adam steps kept of each captured training call
Sig = Tuple[int, int, int, int]


def sig_of(arch) -> Sig:
    return (arch.conv_layers, arch.channels, arch.dense, arch.input_size)


class Probes:
    def __init__(self):
        self.recording = False
        # copy every fresh training call's first steps (and drop them)
        # while warming up, so the copies the window makes compile
        # outside it
        self.warm_capture = False
        self.round = 0
        self.score_frames: Dict[Sig, int] = collections.Counter()
        self.train_samples: Dict[Sig, int] = collections.Counter()
        self.train_steps = 0
        self.train_calls = 0          # train_operator calls seen
        self.train_call_sigs: List[Tuple[Sig, bool]] = []  # (sig, fresh)
        self.capture: Set[int] = set()  # 1-based train call numbers
        self.captures: List[dict] = []
        self.call = None              # the training call being captured
        self.last_crops = (None, None, None)   # FrameBank.crops: out, bank, idxs
        self.trainer = (None, 0)      # CloudTrainer.train: self, answers seen
        self.demands: List[dict] = []
        self.answers: List[tuple] = []
        # a detector reference's own counters and captures
        self.extra: Dict[str, float] = collections.Counter()
        self.extra_captures: Dict[str, list] = collections.defaultdict(list)

    def counts(self) -> dict:
        return {"score_frames": dict(self.score_frames),
                "train_samples": dict(self.train_samples),
                "train_steps": self.train_steps,
                "train_calls": self.train_calls,
                "extra": dict(self.extra)}


def hook(owner, attr: str, after) -> None:
    """Call ``after(out, *args, **kw)`` after ``owner.attr``, with no span."""
    orig = getattr(owner, attr)

    @functools.wraps(orig)
    def wrapped(*args, **kw):
        out = orig(*args, **kw)
        after(out, *args, **kw)
        return out

    setattr(owner, attr, wrapped)


def _span(owner, attr: str, name: str, before=None, after=None) -> None:
    import jax

    orig = getattr(owner, attr)

    @functools.wraps(orig)
    def wrapped(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            if before is not None:
                before(*args, **kw)
            out = orig(*args, **kw)
            if after is not None:
                after(out, *args, **kw)
            return out

    setattr(owner, attr, wrapped)


def install(p: Probes) -> None:
    """Wrap the program's layer entry points (once per process)."""
    import jax
    import jax.numpy as jnp
    from repro.core import operators
    from repro.core.runtime import OperatorRuntime, ScoreBatcher, ScoreHandle
    from repro.core.training import CloudTrainer, FrameBank
    from repro.core.video import Video
    from repro.serving.fleet import FleetService
    from repro.serving.oracle_service import OracleService

    _span(FleetService, "submit", "submit")
    _span(FleetService, "run", "run")
    _span(Video, "render_frames", "render")
    for attr in ("fire_complete", "flush"):
        _span(ScoreBatcher, attr, "score")
    _span(ScoreHandle, "result", "score")
    for attr in ("submit", "step", "flush"):
        _span(OracleService, attr, "verify")

    def on_demand(handle, _batcher, trained, bank, idxs):
        sig = sig_of(trained.arch)
        p.score_frames[sig] += len(idxs)
        if p.recording and len(idxs):
            p.demands.append(dict(
                round=p.round, sig=sig, region=trained.arch.region,
                size=trained.arch.input_size, params=trained.params,
                camera=bank.video.spec.name,
                idxs=np.asarray(idxs, np.int64).copy(), handle=handle))

    _span(ScoreBatcher, "submit", "score", after=on_demand)

    def on_crops(_out, _rt, _params, arch, crops):
        p.score_frames[sig_of(arch)] += len(crops)

    _span(OperatorRuntime, "score_crops", "score", after=on_crops)

    def on_answer(out, _svc, ticket):
        if p.recording:
            d = ticket.demand
            p.answers.append((p.round, d.qid, int(d.idx), d.cls,
                              bool(out[0]), int(out[1])))

    _span(OracleService, "complete", "verify", after=on_answer)

    def on_crops(out, bank, idxs, region, size):
        p.last_crops = (out, bank, idxs)

    hook(FrameBank, "crops", on_crops)

    def on_train(trainer, *_a, **_kw):
        p.trainer = (trainer, len(p.answers))

    _span(CloudTrainer, "train", "train", before=on_train)

    train_operator = operators.train_operator
    bind = inspect.signature(train_operator).bind

    @functools.wraps(train_operator)
    def counted_train(*args, **kw):
        a = bind(*args, **kw)
        a.apply_defaults()
        arch, params, crops = (a.arguments[k] for k in
                               ("arch", "params", "crops"))
        p.train_calls += 1
        fresh = params is None
        p.train_call_sigs.append((sig_of(arch), fresh))
        if fresh and (p.warm_capture or (p.recording
                                         and p.train_calls in p.capture)):
            out, bank, idxs = p.last_crops
            trainer, n_answers = p.trainer
            p.call = dict(
                sig=sig_of(arch), seed=int(a.arguments["seed"]),
                train_count=bool(a.arguments["train_count"]),
                idxs=np.asarray(idxs, np.int64).copy()
                if out is crops else None,
                camera=bank.video.spec.name, region=arch.region,
                size=arch.input_size, trainer=trainer, n_answers=n_answers,
                round=p.round, steps=[])
        try:
            return train_operator(*args, **kw)
        finally:
            call, p.call = p.call, None
            if call is not None and p.recording:
                p.captures.append(call)

    operators.train_operator = counted_train

    factory = operators._adam_step

    @functools.cache
    def counted_factory():
        step = factory()

        def counted(params, m, v, xb, bright, *rest, **kw):
            convs = params["convs"]
            sig = (len(convs), int(convs[0]["w"].shape[-1]),
                   int(params["dense"]["w"].shape[-1]), int(xb.shape[1]))
            p.train_samples[sig] += int(xb.shape[0])
            p.train_steps += 1
            call = p.call
            st = None
            if call is not None and len(call["steps"]) < STEPS:
                # xb is donated to the step: copy it first; the labels
                # are not donated
                st = dict(xb=jnp.copy(xb), bright=np.array(bright),
                          yp=rest[0], yc=rest[1])
            out = step(params, m, v, xb, bright, *rest, **kw)
            if st is not None:
                st["params"] = out[0]       # params are not donated
                if not call["steps"]:
                    st["m"] = jax.tree_util.tree_map(jnp.copy, out[1])
                call["steps"].append(st)
            return out

        return counted

    operators._adam_step = counted_factory
