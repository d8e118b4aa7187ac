"""Configurations and traffic mixes, found by name under ``configs/``
and ``mixes/``, and the one generator that turns them into a world and
a round of queries.

A configuration (``configs/<name>.json``) fixes the deployment: which
corpus scenes are cameras (``scenes``), the archive length
per camera (``hours``), the landmark interval and detector, the
operator family, training steps, the uplink and the precisions. A mix
(``mixes/<traffic>.json``) is the queries one round submits
(``queries``: camera, kind, step arguments); only the cameras it
queries are built.

``--seed`` draws the order in which a round submits its queries. The
world and the queries themselves are the configuration's and the mix's
alone, so every seed does the same work, in another order where a round
holds more than one query.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_mix(traffic: str) -> dict:
    return json.loads((HERE / "mixes" / f"{traffic}.json").read_text())


def load_limits(cell: str) -> dict:
    """The limits of ``correct`` of one cell (``limits/<cell>.json``):
    they are set from the readings of that configuration under that
    traffic."""
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def camera_specs(cfg: dict) -> Dict[str, Tuple[object, str]]:
    """``{camera: (VideoSpec, queried class)}`` in the order the
    configuration lists them."""
    from repro.core.video import QUERY_CLASS, corpus

    scenes = corpus(hours=cfg["hours"])
    return {n: (scenes[n], QUERY_CLASS[n]) for n in cfg["scenes"]}


def build_world(cfg: dict, cameras):
    """``{camera: (Video, LandmarkStore, queried class)}`` of the
    configuration's ``cameras`` that a round queries: the camera side,
    made at capture time."""
    from repro.core import landmarks as lm
    from repro.core.hardware import DETECTORS
    from repro.core.video import Video

    det = DETECTORS[cfg["landmark_detector"]]
    world = {}
    for cam, (spec, cls) in camera_specs(cfg).items():
        if cam not in cameras:
            continue
        video = Video(spec)
        store = lm.build_landmarks(video, cfg["landmark_interval"], det)
        world[cam] = (video, store, cls)
    return world


def round_queries(mix: dict, seed: int) -> List[Tuple[str, str, dict]]:
    """``[(camera, kind, step kwargs)]`` of one round, in the seed's
    order."""
    qs = [(q["camera"], q["kind"], dict(q.get("step", {})))
          for q in mix["queries"]]
    order = np.random.default_rng(seed).permutation(len(qs))
    return [qs[i] for i in order]


def scene_params(spec) -> dict:
    """A VideoSpec as the plain dict ``reference.Scene`` reads."""
    return dataclasses.asdict(spec)
