"""How ``correct`` is decided: what the measured window produced,
against the plain reference (``reference.py``), once the window has
closed.

Five numbers, each with the cell's limit (``limits/<cell>.json``):

  * ``score_prob_gap``: the widest absolute gap in presence probability
    between what the timed path's scoring returned and the reference
    forward at f32 HIGHEST, over a sample of the window's score demands
    drawn from the seed, with the largest demand in it. The reference
    renders and crops the frames itself; the weights are the served
    operator's (the training numbers check how they were made).
  * the training numbers, over a sample of the window's training calls
    that start a new operator (``_trajectory_gaps``): the first
    gradient, the parameters' change over the first ``STEPS`` Adam
    steps, and the losses those steps reach, against the reference
    following the same steps from its own initial weights, on its own
    crops and labels of the frames the program sampled;
  * ``answers_wrong``: queries whose answers disagree with the
    reference world: a query that did not finish, a verification answer
    that is not the cloud detector's, a retrieval that did not return
    every positive frame, a tagging that left a frame untagged, a
    count whose final value is not the one its verified frames give.

With ``control`` the reference in the next precision below the
configuration's (``CONTROL``) stands in the program's place for the
scoring and training numbers, and goes through the same comparison.

The reference runs on the host's CPU: there its many small eager
operations (the Adam update leaf by leaf, the initial weights) cost
milliseconds, where on a TPU each one is a program of its own, to
compile or fetch from the cache, and the check outlasted the run's
time limit. Its precisions are explicit casts, so they read the same
on either backend.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import numpy as np

import reference as ref
from probes import STEPS

CONTROL = {"highest": "high", "high": "bf16", "bf16": "fp8"}
MAX_DEMANDS = 6
MAX_CROPS = 1024
TRAIN_SAMPLES = 4
TRAIN_NUMBERS = ("train_grad_gap", "train_change_gap", "train_loss_gap")


def pick_demands(demands: List[dict], seed: int) -> List[dict]:
    if not demands:
        return []
    big = max(range(len(demands)), key=lambda i: len(demands[i]["idxs"]))
    rest = [i for i in range(len(demands)) if i != big]
    rng = np.random.default_rng([seed, 1])
    take = rng.choice(len(rest), min(MAX_DEMANDS - 1, len(rest)),
                      replace=False) if rest else []
    return [demands[big]] + [demands[rest[i]] for i in sorted(take)]


def pick_train_calls(call_sigs, seed: int, flops) -> List[int]:
    """0-based indices, within one round, of the training calls whose
    first steps are checked: of those that start a new operator, the
    largest by work and a seeded sample."""
    fresh = [i for i, (_sig, new) in enumerate(call_sigs) if new]
    if not fresh:
        return []
    big = max(fresh, key=lambda i: flops(call_sigs[i][0]))
    rest = [i for i in fresh if i != big]
    rng = np.random.default_rng([seed, 2])
    take = rng.choice(len(rest), min(TRAIN_SAMPLES - 1, len(rest)),
                      replace=False) if rest else []
    return sorted({big, *(rest[i] for i in take)})


def scoring(demands, scenes, control: Optional[str]
            ) -> Dict[str, Optional[float]]:
    """The scoring number over the sampled demands. With ``control``,
    the reference at that precision stands in the program's place.

    The count head is not compared: the kernel path's own count error
    (4.8e-6 relative on a TPU v5e) lies within three times of what
    three bf16 passes give, so no limit could part the two."""
    if not demands:
        return {"score_prob_gap": None}
    gap = 0.0
    for d in demands:
        idxs = d["idxs"][:MAX_CROPS]
        x = scenes[d["camera"]].crops(idxs, d["region"], d["size"])
        params = jax.device_get(d["params"])
        rp, _ = ref.forward(params, x, "highest")
        if control:
            p, _ = ref.forward(params, x, control)
        else:
            p = np.asarray(d["handle"].result()[0][:len(idxs)])
        gap = max(gap, float(np.max(np.abs(p - rp))))
    return {"score_prob_gap": gap}


def norm_gap(g: Dict[str, float], r: Dict[str, float]) -> float:
    """Worst leaf: |program's norm - reference's| over the larger of the
    reference leaf's norm and the median leaf's."""
    floor = float(np.median(list(r.values())))
    return max(abs(g[k] - r[k]) / max(r[k], floor, 1e-30) for k in r)


def _labels(cap, scene, cfg, answers_log, task) -> Dict[int, tuple]:
    """``{frame: (label, count)}`` the reference gives the frames of a
    training set: the cloud detector's answer where the query had the
    frame verified before the call, else the camera's landmark answer,
    else the label flow tracking carries (retrieval only)."""
    det = dict(name=cfg["cloud_detector"],
               **cfg["detectors"][cfg["cloud_detector"]])
    lm_det = dict(name=cfg["landmark_detector"],
                  **cfg["detectors"][cfg["landmark_detector"]])
    cls = task.env.query.cls
    verified = {idx for rnd, qid, idx, *_ in answers_log[:cap["n_answers"]]
                if rnd == cap["round"] and qid == task.qid}
    flow = scene.flow_labels(cls, lm_det, cfg["landmark_interval"]) \
        if task.env.query.kind == "retrieval" else {}
    out = {}
    for i in cap["idxs"].tolist():
        if i in verified:
            pos, cnt = scene.answer(i, cls, det)
        elif i % cfg["landmark_interval"] == 0:
            pos, cnt = scene.answer(i, cls, lm_det)
        elif i in flow:
            out[i] = flow[i]
            continue
        else:
            continue                       # no label the reference knows
        out[i] = (1.0 if pos else 0.0, float(cnt))
    return out


def _batches(cap, scene, labels):
    """The reference's own minibatches of the captured steps: each row
    of the program's minibatch is matched to the frame of the training
    set whose reference crop it is, and that frame's crop and labels
    are used. None where a row matches no frame."""
    crops = scene.crops(cap["idxs"], cap["region"], cap["size"])
    row_of = {c.tobytes(): i for i, c in zip(cap["idxs"].tolist(), crops)}
    crop_of = dict(zip(cap["idxs"].tolist(), crops))
    out = []
    for st in cap["steps"]:
        xb = np.asarray(jax.device_get(st["xb"]), np.float32)
        frames = [row_of.get(r.tobytes()) for r in xb]
        if any(f is None or f not in labels for f in frames):
            return None
        out.append((np.stack([crop_of[f] for f in frames]), st["bright"],
                    np.array([labels[f][0] for f in frames], np.float32),
                    np.array([labels[f][1] for f in frames], np.float32)))
    return out


def _trajectory_gaps(prog, ref_run, p0, batches, train_count) -> dict:
    """The three training numbers of one captured call.

    ``prog`` and ``ref_run``: (first gradient, parameters after each
    step). ``train_grad_gap``: the first gradient, by its norm per leaf.
    ``train_change_gap``: the parameters' change over the steps, by its
    norm per leaf, over the leaves whose reference gradient is not
    nought to rounding (under a thousandth of the median leaf's).
    ``train_loss_gap``: at each step after the first, the loss of the
    parameters that step starts from on its own minibatch, program
    against reference, over the most the reference's steps moved it."""
    g_prog, after_prog = prog
    g_ref, after_ref = ref_run
    gn = ref.leaf_norms(g_ref)
    med = float(np.median(list(gn.values())))
    live = {k for k, n in gn.items() if n >= 1e-3 * med}
    tsub = jax.tree_util.tree_map

    def change(after):
        return {k: n for k, n in ref.leaf_norms(
            tsub(lambda a, b: np.asarray(a, np.float64)
                 - np.asarray(b, np.float64), after[-1], p0)).items()
                if k in live}

    num, den = 0.0, 0.0
    for k in range(1, len(batches)):
        lp = ref.loss(after_prog[k - 1], batches[k], train_count)
        lr_ = ref.loss(after_ref[k - 1], batches[k], train_count)
        l0 = ref.loss(p0, batches[k], train_count)
        num, den = max(num, abs(lp - lr_)), max(den, abs(l0 - lr_))
    return {"train_grad_gap": norm_gap(ref.leaf_norms(g_prog), gn),
            "train_change_gap": norm_gap(change(after_prog),
                                         change(after_ref)),
            "train_loss_gap": num / max(den, 1e-30)}


def training(captures, scenes, cfg, answers_log, tasks,
             control: Optional[str]) -> Dict[str, Optional[float]]:
    """The training numbers over the captured calls (worst call). With
    ``control``, the reference at that precision stands in the
    program's place."""
    worst = dict.fromkeys(TRAIN_NUMBERS)
    for cap in captures:
        task = tasks.get(id(cap["trainer"]))
        scene = scenes[cap["camera"]]
        batches = None
        if task is not None and cap["idxs"] is not None and \
                len(cap["steps"]) == STEPS:
            batches = _batches(cap, scene, _labels(cap, scene, cfg,
                                                   answers_log, task))
        if batches is None:
            # a minibatch that is not the training set's frames
            gaps = dict.fromkeys(TRAIN_NUMBERS, math.inf)
        else:
            p0 = ref.init(cap["sig"], cap["seed"])
            hp = cfg["training"]
            ref_run = ref.adam(p0, batches, hp, cap["train_count"])
            if control:
                prog = ref.adam(p0, batches, hp, cap["train_count"], control)
            else:
                steps = cap["steps"]
                m1 = jax.device_get(steps[0]["m"])
                prog = (jax.tree_util.tree_map(
                    lambda m: np.asarray(m, np.float64) / (1 - hp["beta1"]),
                    m1), [jax.device_get(st["params"]) for st in steps])
            gaps = _trajectory_gaps(prog, ref_run, p0, batches,
                                    cap["train_count"])
        for k, v in gaps.items():
            worst[k] = v if worst[k] is None else max(worst[k], v)
    return worst


def answers(rounds, scenes, cfg, answers_log) -> List[str]:
    """``"round/qid: reason"`` of every query whose answers disagree
    with the reference world."""
    det = dict(name=cfg["cloud_detector"],
               **cfg["detectors"][cfg["cloud_detector"]])
    lm_det = dict(name=cfg["landmark_detector"],
                  **cfg["detectors"][cfg["landmark_detector"]])
    nf = int(cfg["hours"] * 3600 * cfg["fps"])
    frames = np.arange(nf)
    lm_idxs = np.arange(0, nf, cfg["landmark_interval"])
    truth = {}

    def gt(cam, cls):
        if (cam, cls) not in truth:
            a = [scenes[cam].answer(i, cls, det) for i in frames]
            truth[cam, cls] = (np.array([x[0] for x in a]),
                               np.array([x[1] for x in a]))
        return truth[cam, cls]

    by_query: Dict[tuple, list] = {}
    for rnd, qid, idx, cls, pos, cnt in answers_log:
        by_query.setdefault((rnd, qid), []).append((idx, cls, pos, cnt))
    wrong = []
    for rnd, r in enumerate(rounds, start=1):
        for task in r["tasks"]:
            kind, cls = task.env.query.kind, task.env.query.cls
            why = _query_fault(kind, cls, task, by_query.get(
                (rnd, task.qid), []), gt(task.camera, cls),
                [scenes[task.camera].answer(i, cls, lm_det)[1]
                 for i in lm_idxs])
            if why:
                wrong.append(f"{rnd}/{task.qid}: {why}")
    return wrong


def _query_fault(kind, cls, task, got, truth, lm_counts) -> str:
    """Why one query's answers are wrong, or "" where they are right."""
    pos_gt, cnt_gt = truth
    prog = task.result
    if prog is None or prog.done_t is None or not math.isfinite(prog.done_t):
        return "no final answer"
    for i, c, pos, cnt in got:
        if c != cls or pos != bool(pos_gt[i]) or cnt != int(cnt_gt[i]):
            return f"frame {i} verified as ({pos}, {cnt}), detector says " \
                f"({bool(pos_gt[i])}, {int(cnt_gt[i])})"
    final = prog.points[-1][1] if prog.points else None
    if kind == "retrieval":
        found = {i for i, _, pos, _ in got if pos}
        missed = set(np.nonzero(pos_gt)[0].tolist()) - found
        if missed or len(found) != int(pos_gt.sum()):
            return f"retrieval missed {len(missed)} positive frames"
    elif kind == "tagging":
        tags = task.executor.tags
        if np.any(tags == 0):
            return f"{int(np.sum(tags == 0))} frames untagged"
        cloud = np.nonzero(tags >= 3)[0]
        if np.any((tags[cloud] == 4) != pos_gt[cloud]):
            return "a cloud tag disagrees with the detector"
    elif kind.startswith("count_"):
        seen = [cnt for _, _, _, cnt in got]
        if kind == "count_max":
            gmax = int(cnt_gt.max())
            best = max(lm_counts + seen, default=0)
            want = 1.0 if best >= gmax else best / max(gmax, 1)
        else:
            stat = np.median if kind == "count_median" else np.mean
            g = float(stat(cnt_gt))
            e = float(stat(lm_counts + seen)) if lm_counts + seen else 0.0
            want = max(0.0, 1.0 - abs(e - g) / max(abs(g), 1e-6))
        if final is None or abs(final - want) > 1e-9:
            return f"final value {final!r}, its frames give {want!r}"
    return ""


def run(cfg, limits, world_params, probes, rounds, seed: int, *,
        control: bool, scored: bool, trained: bool):
    """``({name: {"value", "limit"}}, the wrong answers)``.

    With ``control`` the reference one precision below the
    configuration's stands in the program's place for the scoring and
    training numbers, which are then compared as the program's are. A
    window that scored nothing (or trained nothing) has no scoring (or
    training) number to compare, and leaves it out; one that did but
    yields no reading reports None, which fails."""
    with jax.default_device(jax.devices("cpu")[0]):
        return _run(cfg, limits, world_params, probes, rounds, seed,
                    control, scored, trained)


def _run(cfg, limits, world_params, probes, rounds, seed, control, scored,
         trained):
    scenes = {cam: ref.Scene(p) for cam, p in world_params.items()}
    prec = cfg["precision"]
    values = {}
    if scored:
        values.update(scoring(pick_demands(probes.demands, seed), scenes,
                              CONTROL[prec["score"]] if control else None))
    if trained:
        tasks = {id(t.env.trainer): t for r in rounds for t in r["tasks"]}
        values.update(training(probes.captures, scenes, cfg, probes.answers,
                               tasks,
                               CONTROL[prec["train"]] if control else None))
    wrong = answers(rounds, scenes, cfg, probes.answers)
    values["answers_wrong"] = len(wrong)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    return checks, wrong
