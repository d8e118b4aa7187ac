"""Configurations, traffic mixes and detector references, found by name
under ``configs/``, ``mixes/`` and ``detectors/``, and the one generator
that turns them into a world and a round of queries.

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
import importlib.util
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


def load_module(path: Path, name: str):
    """The Python file at ``path``, loaded as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def detectors(cfg: dict) -> Dict[str, dict]:
    """``{name: detector}`` of the configuration's ``detectors``: each
    entry with its ``name`` added and, where it names a ``reference``,
    that module under ``module``. Each module is loaded once, so that
    its ``install`` and its ``answer`` and ``numbers`` are one module."""
    mods, out = {}, {}
    for name, entry in cfg["detectors"].items():
        det = dict(entry, name=name)
        ref = entry.get("reference")
        if ref is not None:
            if ref not in mods:
                mods[ref] = load_module(HERE / "detectors" / f"{ref}.py",
                                        f"chipbench_detector_{ref}")
            det["module"] = mods[ref]
        out[name] = det
    return out


def reference_modules(dets: Dict[str, dict]) -> list:
    """The distinct detector reference modules of ``dets``."""
    return list({id(d["module"]): d["module"] for d in dets.values()
                 if "module" in d}.values())


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
