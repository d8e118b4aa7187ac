"""The plain reference: what the served path should produce, computed
with nothing of the program.

  * ``Scene`` replays a camera's footage from its scene parameters (the
    object events, the background, the per-frame sensor noise), renders
    frames and crops them the way the cloud's frame cache stores them
    (uint8 frames, uint8 crops, nearest-neighbour resize), and answers
    as the cloud's YOLOv3-tier detector does (seeded per scene, frame
    and detector).
  * ``forward`` is an operator's forward pass in plain ``jax.numpy``:
    3x3 stride-2 SAME convolutions with bias and ReLU, a dense ReLU
    layer and a two-output head (presence probability, count).
  * ``flow_labels`` is the camera's optical-flow label amplification:
    labels carried from each landmark into its neighbouring frames
    until the (seeded) track is lost.
  * ``init`` draws a new operator's weights from its seed; ``adam``
    follows the first Adam steps from them: the training loss
    (brightness augmentation, BCE plus 0.3 Huber on the count), its
    gradient and the update with bias correction and weight decay.

``precision`` names how products are rounded: "highest" is f32;
"high" emulates three bf16 passes (hi*hi + hi*lo + lo*hi), "bf16" one
pass, "fp8" one pass on float8_e4m3fn operands scaled per tensor. The
emulation is explicit casts, so it reads the same on every backend.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FRAME = 96
E4M3_MAX = 448.0
_HIGHEST = jax.lax.Precision.HIGHEST


# -- the world ---------------------------------------------------------------


class Scene:
    """One camera's footage from its parameters (a plain dict: seed,
    hours, fps, night, bg_complexity, classes)."""

    def __init__(self, p: dict):
        self.p = p
        self.classes = p["classes"]
        self.n_frames = int(p["hours"] * 3600 * p["fps"])
        rng = np.random.default_rng(p["seed"])
        total_s = p["hours"] * 3600
        ev = []
        for cs in self.classes:
            prof = np.asarray(cs["hour_profile"], np.float64)
            prof = prof / prof.mean()
            n = rng.poisson(cs["rate_per_hour"] * p["hours"])
            t0s = rng.uniform(0, total_s, size=n)
            hours = ((t0s / 3600) % 24).astype(int)
            keep = rng.uniform(0, 1, size=n) < prof[hours] / max(prof.max(),
                                                                1e-9)
            for t0 in t0s[keep]:
                dur = rng.uniform(*cs["duration_s"])
                y = np.clip(rng.normal(cs["region_center"][0],
                                       cs["region_sd"][0]), 0.02, 0.98) * FRAME
                x = np.clip(rng.normal(cs["region_center"][1],
                                       cs["region_sd"][1]), 0.02, 0.98) * FRAME
                size = int(rng.integers(cs["size"][0], cs["size"][1] + 1))
                ev.append((cs["name"], t0, t0 + dur, y, x, size,
                           rng.uniform(-0.15, 0.15)))
        ev.sort(key=lambda e: e[1])
        self.events = ev
        self._t0 = np.array([e[1] for e in ev])
        self._t1 = np.array([e[2] for e in ev])
        base = rng.uniform(60, 120, size=3)
        yy, xx = np.mgrid[0:FRAME, 0:FRAME].astype(np.float64)
        tex = np.sin(yy / 9.0) + np.cos(xx / 13.0) + 0.5 * np.sin((xx + yy)
                                                                  / 7.0)
        self.bg = np.clip(base[None, None, :] + p["bg_complexity"] * 22
                          * tex[..., None], 0, 255)
        self.color = {cs["name"]: cs["color"] for cs in self.classes}

    def _on_screen(self, idx: int) -> List[tuple]:
        t = float(idx) / self.p["fps"]
        sel = np.nonzero((self._t0 <= t) & (self._t1 > t))[0]
        return [self.events[i] for i in sel]

    def boxes(self, idx: int) -> List[tuple]:
        t = float(idx) / self.p["fps"]
        out = []
        for cls, t0, _t1, y, x, size, wobble in self._on_screen(idx):
            drift = wobble * (t - t0)
            y, x, h = y + drift, x + drift * 0.3, size / 2
            y0, x0 = max(0, y - h), max(0, x - h)
            y1, x1 = min(FRAME, y + h), min(FRAME, x + h)
            if y1 > y0 and x1 > x0:
                out.append((cls, y0, x0, y1, x1))
        return out

    def render(self, idx: int) -> np.ndarray:
        """One (H, W, 3) float32 frame in [0, 1]."""
        t = float(idx) / self.p["fps"]
        lum = max(0.55 + 0.45 * np.sin(((t / 3600) % 24 - 6) / 24 * 2
                                        * np.pi), 0.25)
        img = self.bg * lum
        for cls, t0, _t1, y, x, size, wobble in self._on_screen(idx):
            drift = wobble * (t - t0)
            y, x, h = y + drift, x + drift * 0.3, size / 2
            y0, y1 = int(max(0, y - h)), int(min(FRAME, y + h))
            x0, x1 = int(max(0, x - h)), int(min(FRAME, x + h))
            if y1 <= y0 or x1 <= x0:
                continue
            color = np.array(self.color[cls], np.float64) * lum
            img = img.copy() if img is self.bg else img
            img[y0:y1, x0:x1] = 0.25 * img[y0:y1, x0:x1] + 0.75 * color
        rng = np.random.default_rng((self.p["seed"] * 1_000_003 + int(idx))
                                    & 0x7FFFFFFF)
        img = img + rng.normal(0, 14.0 if self.p["night"] else 6.0,
                               size=img.shape)
        return (np.clip(img, 0, 255) / 255.0).astype(np.float32)

    def crops(self, idxs: Sequence[int], region, size: int) -> np.ndarray:
        """(N, size, size, 3) float32: frames kept as uint8, cropped to
        ``region`` (y0, x0, y1, x1; None is the whole frame), resized by
        nearest neighbour, kept as uint8 again."""
        frames = np.stack([(self.render(i) * 255).astype(np.uint8)
                           for i in idxs]).astype(np.float32) / 255.0
        y0, x0, y1, x1 = region if region else (0, 0, FRAME, FRAME)
        c = frames[:, int(y0):int(y1), int(x0):int(x1), :]
        h, w = c.shape[1:3]
        ys = np.clip((np.arange(size) + 0.5) * h / size, 0, h - 1).astype(int)
        xs = np.clip((np.arange(size) + 0.5) * w / size, 0, w - 1).astype(int)
        c = c[:, ys][:, :, xs]
        return (c * 255).astype(np.uint8).astype(np.float32) / 255.0

    def detect(self, idx: int, det: dict) -> List[str]:
        """Classes the cloud detector reports in frame ``idx`` (one entry
        per detection). ``det``: name and accuracy."""
        key = f"{self.p['seed']}|{int(idx)}|{det['name']}".encode()
        rng = np.random.default_rng(zlib.crc32(key) & 0x7FFFFFFF)
        acc = det["accuracy"]
        out = []
        for cls, y0, x0, y1, x1 in self.boxes(idx):
            size = max(y1 - y0, x1 - x0)
            sf = np.clip((size - 4.0) / 24.0, 0.05, 1.0) ** 0.5
            if rng.uniform() < float(np.clip(acc * (0.55 + 0.45 * sf), 0, 1)):
                rng.normal(0, (1.0 - acc) * size * 0.3, 2)   # box jitter
                out.append(cls)
        names = [cs["name"] for cs in self.classes]
        for _ in range(rng.poisson((1.0 - acc) * 0.6)):
            out.append(names[rng.integers(len(names))])
            rng.uniform(0, FRAME), rng.uniform(0, FRAME), rng.uniform(6, 20)
        return out

    def answer(self, idx: int, cls: str, det: dict) -> Tuple[bool, int]:
        n = sum(1 for c in self.detect(idx, det) if c == cls)
        return n > 0, n

    def flow_labels(self, landmarks: Dict[int, Tuple[bool, int]],
                    step_success: float = 0.92, leave_p: float = 0.12,
                    reach: int = 12) -> Dict[int, Tuple[float, float, int]]:
        """``{frame: (label, count, landmark)}`` that flow tracking
        carries from each landmark (``landmarks``: frame -> the landmark
        detector's presence and count) into up to ``reach`` frames each
        way: each step the track holds with ``step_success``, and a
        tracked object leaves the view with ``leave_p`` (seeded per scene
        and landmark)."""
        n = self.n_frames
        out = {}
        for li, (label, cnt) in sorted(landmarks.items()):
            key = f"flow|{self.p['seed']}|{li}".encode()
            rng = np.random.default_rng(zlib.crc32(key) & 0x7FFFFFFF)
            for direction in (-1, 1):
                lab, c = label, cnt
                for k in range(1, reach + 1):
                    j = li + direction * k
                    if j < 0 or j >= n or rng.uniform() > step_success:
                        break
                    if lab and rng.uniform() < leave_p:
                        lab, c = False, 0
                    out[j] = (1.0 if lab else 0.0, float(c), li)
        return out


# -- the operator ------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(a, precision: str):
    """Operand rounding of one product pass at ``precision``: bf16, or
    float8_e4m3fn scaled per tensor to its largest value. The gradient
    passes through unrounded, so only the products see the rounding."""
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    return a


_round.defvjp(lambda a, precision: (_round(a, precision), None),
              lambda precision, _res, g: (g,))


def _product(op, a, b, precision: str):
    """``op(a, b)`` (a conv or a matmul) with operands rounded as
    ``precision`` says; each pass itself runs in f32 at HIGHEST."""
    if precision == "high":
        ah = _round(a, "bf16")
        bh = _round(b, "bf16")
        al = _round(a - ah, "bf16")
        bl = _round(b - bh, "bf16")
        return op(ah, bh) + op(ah, bl) + op(al, bh)
    return op(_round(a, precision), _round(b, precision))


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(2, 2), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HIGHEST)


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST)


def _outputs(params, x, precision: str):
    h = x
    for c in params["convs"]:
        h = jax.nn.relu(_product(_conv, h, c["w"], precision) + c["b"])
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_product(_dot, h, params["dense"]["w"], precision)
                    + params["dense"]["b"])
    return _product(_dot, h, params["head"]["w"], precision) \
        + params["head"]["b"]


def forward(params, x, precision: str = "highest"):
    """(presence probability, count) per frame, as float64 numpy."""
    out = jax.jit(_outputs, static_argnums=2)(params, jnp.asarray(x),
                                              precision)
    return (np.asarray(jax.nn.sigmoid(out[:, 0]), np.float64),
            np.asarray(jax.nn.softplus(out[:, 1]), np.float64))


def _loss(params, x, bright, y_present, y_count, train_count, precision):
    out = _outputs(params, jnp.clip(x * bright, 0.0, 1.0), precision)
    logit, cnt = out[:, 0], jax.nn.softplus(out[:, 1])
    bce = jnp.mean(jnp.maximum(logit, 0) - logit * y_present
                   + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    if not train_count:
        return bce
    err = jnp.abs(cnt - y_count)
    huber = jnp.mean(jnp.where(err < 2.0, 0.5 * err ** 2, 2.0 * err - 2.0))
    return bce + 0.3 * huber


def init(sig, seed: int) -> dict:
    """A new operator's weights: ``sig`` is (conv layers, channels,
    dense width, input size); He-normal convs and dense layer, a
    1/fan-in head, zero biases, from ``PRNGKey(seed)`` split once per
    layer."""
    layers, ch, dense, size = sig
    ks = jax.random.split(jax.random.PRNGKey(seed), layers + 2)
    params = {"convs": []}
    c_in, s = 3, size
    for i in range(layers):
        w = jax.random.normal(ks[i], (3, 3, c_in, ch)) \
            * (2.0 / (9 * c_in)) ** 0.5
        params["convs"].append({"w": w, "b": jnp.zeros((ch,))})
        c_in = ch
        s = max(1, (s + 1) // 2)
    feat = s * s * c_in
    params["dense"] = {
        "w": jax.random.normal(ks[-2], (feat, dense)) * (2.0 / feat) ** 0.5,
        "b": jnp.zeros((dense,))}
    params["head"] = {
        "w": jax.random.normal(ks[-1], (dense, 2)) * (1.0 / dense) ** 0.5,
        "b": jnp.zeros((2,))}
    return params


_grad = jax.jit(jax.value_and_grad(_loss), static_argnums=(5, 6))
_value = jax.jit(_loss, static_argnums=(5, 6))


def loss(params, batch, train_count: bool) -> float:
    """The training loss of ``params`` on ``batch`` (x, bright,
    y_present, y_count) at f32."""
    return float(_value(params, *map(jnp.asarray, batch), bool(train_count),
                        "highest"))


def adam(params, batches, hp: dict, train_count: bool,
         precision: str = "highest"):
    """Adam from ``params`` over ``batches``, one step each.

    Returns (the first step's gradient, the parameters after each step).
    ``hp``: lr, beta1, beta2, eps, weight_decay (decoupled: the
    parameters are scaled by 1 - lr * weight_decay each step)."""
    tmap = jax.tree_util.tree_map
    b1, b2, lr = hp["beta1"], hp["beta2"], hp["lr"]
    m = tmap(jnp.zeros_like, params)
    v = tmap(jnp.zeros_like, params)
    g1, after = None, []
    for t, batch in enumerate(batches, start=1):
        _, g = _grad(params, *map(jnp.asarray, batch), bool(train_count),
                     precision)
        g1 = g if g1 is None else g1
        m = tmap(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = tmap(lambda v_, g_: b2 * v_ + (1 - b2) * g_ ** 2, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        decay = 1 - lr * hp["weight_decay"]
        params = tmap(lambda p, m_, v_: decay * p - lr * (m_ / bc1)
                      / (jnp.sqrt(v_ / bc2) + hp["eps"]), params, m, v)
        after.append(params)
    return g1, after


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64))) for k, v in flat}
