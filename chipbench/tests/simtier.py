"""A detector reference by file, for ``test_discovery.py``, which copies
it to ``detectors/simtier.py``: the seeded tier of ``reference.Scene``
restated, not sure of every 23rd frame from frame 7, with a counter and
a capture of the answers the oracle service gives, and one number.

``FLIP`` (set in the test's copies) flips this reference's presence in
one frame: ``"unsure"`` in frame 7, which it is not sure of, ``"sure"``
in frame 1, which it is."""
from __future__ import annotations

import numpy as np

import probes as probes_mod

NAME = "simtier"
FLIP = ""
FRAME = {"unsure": 7, "sure": 1}


def answer(scene, idxs, cls, det):
    idxs = np.asarray(idxs, np.int64)
    a = [scene.answer(int(i), cls, det) for i in idxs]
    pos = np.array([p for p, _ in a], bool)
    cnt = np.array([c for _, c in a], np.int64)
    if FLIP:
        pos ^= idxs == FRAME[FLIP]
    return pos, cnt, idxs % 23 != 7


def install(p) -> None:
    from repro.serving.oracle_service import OracleService

    def on_complete(out, _svc, ticket):
        p.extra["simtier_answers"] += 1
        if p.recording:
            p.extra_captures[NAME].append((
                ticket.lane.env.video.spec.name, int(ticket.demand.idx),
                ticket.demand.cls, bool(out[0]), int(out[1])))

    probes_mod.hook(OracleService, "complete", on_complete)


def numbers(p, scenes, cfg, seed):
    """``simtier_capture_wrong``: captured answers, on frames this
    reference is sure of, that are not its own."""
    name = cfg["cloud_detector"]
    det = dict(cfg["detectors"][name], name=name)
    wrong = 0
    for cam, idx, cls, pos, cnt in p.extra_captures[NAME]:
        rp, rc, sure = answer(scenes[cam], [idx], cls, det)
        wrong += bool(sure[0] and (rp[0] != pos or rc[0] != cnt))
    return {"simtier_capture_wrong": wrong}
