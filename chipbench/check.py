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

A detector reference module adds the numbers its ``numbers`` returns.

Every detector answer comes through ``answer``: from the module
``detectors/<reference>.py`` where the configuration's entry of that
detector names one, else from the seeded tiers of ``reference.Scene``.
A frame the reference is not ``sure`` of (its score within the module's
margin of its threshold, where rounding can flip it) is not held
against the program: there the reference keeps the program's answer.

With ``control`` the reference in the next precision below the
configuration's (``CONTROL``) stands in the program's place for the
scoring and training numbers, and goes through the same comparison.

The reference runs on the host's CPU: there its many small eager
operations (the Adam update leaf by leaf, the initial weights) cost
milliseconds, where on a TPU each one is a program of its own, to
compile or fetch from the cache, and the check outlasted the run's
time limit. Its precisions are explicit casts, so they read the same
on either backend. A detector reference module decides where its own
reference runs: a network over every frame of a queried range is too
slow for the CPU, and may put its arrays on the TPU explicitly and run
jitted at f32 ``Precision.HIGHEST`` (the check runs after the window
has closed and the device's peak memory has been read).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import numpy as np

import reference as ref
import traffic
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


def answer(scene, idxs, cls: str, det: dict):
    """The reference detector ``det``'s answers in frames ``idxs`` of
    ``scene``: presence (bool), count (int) and ``sure`` (bool), one
    each per frame, as numpy arrays.

    A detector whose entry names a ``reference`` answers through that
    module's ``answer(scene, idxs, cls, det)``, which returns the same
    three; ``sure`` is False where its score lies within the module's
    stated margin of its threshold. The others are the seeded tiers of
    ``reference.Scene``, sure of every frame."""
    mod = det.get("module")
    if mod is not None:
        pos, cnt, sure = mod.answer(scene, idxs, cls, det)
    else:
        a = [scene.answer(int(i), cls, det) for i in idxs]
        pos, cnt = [p for p, _ in a], [c for _, c in a]
        sure = np.ones(len(a), bool)
    return (np.asarray(pos, bool), np.asarray(cnt, np.int64),
            np.asarray(sure, bool))


def norm_gap(g: Dict[str, float], r: Dict[str, float]) -> float:
    """Worst leaf: |program's norm - reference's| over the larger of the
    reference leaf's norm and the median leaf's."""
    floor = float(np.median(list(r.values())))
    return max(abs(g[k] - r[k]) / max(r[k], floor, 1e-30) for k in r)


def _labels(cap, scene, cfg, dets, answers_log, task) -> Dict[int, tuple]:
    """``{frame: (label, count, sure)}`` the reference gives the frames
    of a training set: the cloud detector's answer where the query had
    the frame verified before the call, else the camera's landmark
    answer, else the label flow tracking carries from a landmark
    (retrieval only), as sure as that landmark's answer."""
    cls = task.env.query.cls
    every = cfg["landmark_interval"]
    verified = {idx for rnd, qid, idx, *_ in answers_log[:cap["n_answers"]]
                if rnd == cap["round"] and qid == task.qid}
    idxs = cap["idxs"].tolist()
    retrieval = task.env.query.kind == "retrieval"
    # flow labels need every landmark of the archive
    lm_frames = list(range(0, scene.n_frames, every)) if retrieval else \
        [i for i in idxs if i % every == 0]
    labels = {}
    for frames, det in ((lm_frames, dets[cfg["landmark_detector"]]),
                        ([i for i in idxs if i in verified],
                         dets[cfg["cloud_detector"]])):
        pos, cnt, sure = answer(scene, frames, cls, det)
        labels.update((i, (float(p), float(c), bool(s)))
                      for i, p, c, s in zip(frames, pos, cnt, sure))
    if retrieval:
        lms = {i: labels[i] for i in lm_frames}
        flow = scene.flow_labels({i: (v[0] > 0, int(v[1]))
                                  for i, v in lms.items()})
        for i, (label, cnt, li) in flow.items():
            labels.setdefault(i, (label, cnt, lms[li][2]))
    return {i: labels[i] for i in idxs if i in labels}


def _batches(cap, scene, labels):
    """The reference's own minibatches of the captured steps: each row
    of the program's minibatch is matched to the frame of the training
    set whose reference crop it is, and that frame's crop and labels
    are used; where the reference is not sure of a frame's label, the
    row keeps the program's. None where a row matches no frame."""
    crops = scene.crops(cap["idxs"], cap["region"], cap["size"])
    row_of = {c.tobytes(): i for i, c in zip(cap["idxs"].tolist(), crops)}
    crop_of = dict(zip(cap["idxs"].tolist(), crops))
    out = []
    for st in cap["steps"]:
        xb = np.asarray(jax.device_get(st["xb"]), np.float32)
        frames = [row_of.get(r.tobytes()) for r in xb]
        if any(f is None or f not in labels for f in frames):
            return None
        lab = np.array([labels[f] for f in frames], np.float64)
        sure = lab[:, 2] > 0
        out.append((np.stack([crop_of[f] for f in frames]), st["bright"],
                    np.where(sure, lab[:, 0], jax.device_get(st["yp"]))
                    .astype(np.float32),
                    np.where(sure, lab[:, 1], jax.device_get(st["yc"]))
                    .astype(np.float32)))
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


def training(captures, scenes, cfg, dets, answers_log, tasks,
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
            batches = _batches(cap, scene, _labels(cap, scene, cfg, dets,
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


def answers(rounds, scenes, cfg, dets, answers_log) -> List[str]:
    """``"round/qid: reason"`` of every query whose answers disagree
    with the reference world."""
    nf = int(cfg["hours"] * 3600 * cfg["fps"])
    frames = np.arange(nf)
    lm_idxs = np.arange(0, nf, cfg["landmark_interval"])
    truth = {}

    def gt(cam, cls, of):
        """The cloud detector's answers over the archive, or the
        landmark detector's over the landmarks (``of``)."""
        if (cam, cls, of) not in truth:
            idxs, det = (frames, cfg["cloud_detector"]) if of == "cloud" \
                else (lm_idxs, cfg["landmark_detector"])
            truth[cam, cls, of] = answer(scenes[cam], idxs, cls, dets[det])
        return truth[cam, cls, of]

    by_query: Dict[tuple, list] = {}
    for rnd, qid, idx, cls, pos, cnt in answers_log:
        by_query.setdefault((rnd, qid), []).append((idx, cls, pos, cnt))
    wrong = []
    for rnd, r in enumerate(rounds, start=1):
        for task in r["tasks"]:
            kind, cls = task.env.query.kind, task.env.query.cls
            why = _query_fault(kind, cls, task, by_query.get(
                (rnd, task.qid), []), gt(task.camera, cls, "cloud"),
                gt(task.camera, cls, "landmark"), lm_idxs)
            if why:
                wrong.append(f"{rnd}/{task.qid}: {why}")
    return wrong


def _query_fault(kind, cls, task, got, truth, lm_truth, lm_idxs) -> str:
    """Why one query's answers are wrong, or "" where they are right.
    A frame the reference is not sure of is not held against the
    query: its answer there is not compared, and a count takes the
    program's own count of it."""
    pos_gt, cnt_gt, sure = truth
    prog = task.result
    if prog is None or prog.done_t is None or not math.isfinite(prog.done_t):
        return "no final answer"
    for i, c, pos, cnt in got:
        if c != cls or sure[i] and (pos != bool(pos_gt[i])
                                    or cnt != int(cnt_gt[i])):
            return f"frame {i} verified as ({pos}, {cnt}), detector says " \
                f"({bool(pos_gt[i])}, {int(cnt_gt[i])})"
    final = prog.points[-1][1] if prog.points else None
    if kind == "retrieval":
        found = {i for i, _, pos, _ in got if pos and sure[i]}
        missed = set(np.nonzero(pos_gt & sure)[0].tolist()) - found
        if missed or len(found) != int((pos_gt & sure).sum()):
            return f"retrieval missed {len(missed)} positive frames"
    elif kind == "tagging":
        tags = task.executor.tags
        if np.any(tags == 0):
            return f"{int(np.sum(tags == 0))} frames untagged"
        cloud = np.nonzero((tags >= 3) & sure)[0]
        if np.any((tags[cloud] == 4) != pos_gt[cloud]):
            return "a cloud tag disagrees with the detector"
    elif kind.startswith("count_"):
        cnt_gt = np.where(sure, cnt_gt, task.env.gt_count)
        _, lm_cnt, lm_sure = lm_truth
        prog_lm = {lm.idx: sum(1 for d in lm.detections if d[0] == cls)
                   for lm in task.env.store.landmarks}
        lm_counts = [int(c) if s else prog_lm[int(i)]
                     for i, c, s in zip(lm_idxs, lm_cnt, lm_sure)]
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


def run(cfg, dets, limits, world_params, probes, rounds, seed: int, *,
        control: bool, scored: bool, trained: bool):
    """``({name: {"value", "limit"}}, the wrong answers)``.

    ``dets``: the configuration's detectors (``traffic.detectors``).
    With ``control`` the reference one precision below the
    configuration's stands in the program's place for the scoring and
    training numbers, which are then compared as the program's are. A
    window that scored nothing (or trained nothing) has no scoring (or
    training) number to compare, and leaves it out; one that did but
    yields no reading reports None, which fails.

    The CPU is the default device here; a detector reference module
    that runs a network may still place its arrays on the TPU."""
    with jax.default_device(jax.devices("cpu")[0]):
        return _run(cfg, dets, limits, world_params, probes, rounds, seed,
                    control, scored, trained)


def _run(cfg, dets, limits, world_params, probes, rounds, seed, control,
         scored, trained):
    scenes = {cam: ref.Scene(p) for cam, p in world_params.items()}
    prec = cfg["precision"]
    values = {}
    if scored:
        values.update(scoring(pick_demands(probes.demands, seed), scenes,
                              CONTROL[prec["score"]] if control else None))
    if trained:
        tasks = {id(t.env.trainer): t for r in rounds for t in r["tasks"]}
        values.update(training(probes.captures, scenes, cfg, dets,
                               probes.answers, tasks,
                               CONTROL[prec["train"]] if control else None))
    wrong = answers(rounds, scenes, cfg, dets, probes.answers)
    values["answers_wrong"] = len(wrong)
    for mod in traffic.reference_modules(dets):
        if hasattr(mod, "numbers"):
            values.update(mod.numbers(probes, scenes, cfg, seed))
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    return checks, wrong
