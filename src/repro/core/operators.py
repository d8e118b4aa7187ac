"""On-camera operator family: AlexNet-style micro-CNNs in pure JAX (§7).

Variants span conv depth (2-5), channel width (8/16/32), dense width
(16/32/64) and input size (25/50/100), times an input *region* carved
from the spatial-skew heatmap — exactly the paper's breeding axes.
Each operator outputs (presence_logit, count): rankers sort frames by
presence probability (Retrieval) or predicted count (max-Count);
filters threshold presence probability with calibrated (lo, hi).

Batched inference goes through ``core/runtime.OperatorRuntime``, which
jit-compiles one scoring function per arch signature. On a TPU host the
conv stack runs in the Pallas ``kernels/conv_scorer`` kernel, which is
required there: a shape it cannot compile raises, it never falls back.
CPU hosts run the jnp reference. The unjitted ``apply_operator`` /
``score_frames`` below are the mathematical oracle that training and
the runtime's correctness tests compare against.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as _kops


@dataclass(frozen=True)
class OperatorArch:
    name: str
    conv_layers: int          # 2..5
    channels: int             # 8 | 16 | 32
    dense: int                # 16 | 32 | 64
    input_size: int           # 25 | 50 | 100
    region: Optional[Tuple[int, int, int, int]] = None  # frame crop (px)

    @property
    def flops(self) -> float:
        """Per-frame inference cost model (drives the camera FPS).

        Charges AlexNet-style stride-1 conv + 2x2 pool per layer — the
        paper's actual operator family — which calibrates the family to
        the measured 27x-1000x-realtime band on Rpi3 (§8). The host
        executes a stride-2 surrogate with the same accuracy trends;
        simulated time always uses this model (DESIGN.md §8)."""
        s = self.input_size
        c_in = 3
        total = 0.0
        for i in range(self.conv_layers):
            # stride-1 SAME conv at s x s, then 2x2 pool
            total += 2.0 * s * s * self.channels * 9 * c_in
            c_in = self.channels
            s = max(1, (s + 1) // 2)
        feat = s * s * c_in
        total += 2.0 * feat * self.dense + 2.0 * self.dense * 2
        return total

    @property
    def param_count(self) -> int:
        c_in, s = 3, self.input_size
        n = 0
        for _ in range(self.conv_layers):
            n += 9 * c_in * self.channels + self.channels
            c_in = self.channels
            s = max(1, (s + 1) // 2)
        n += s * s * c_in * self.dense + self.dense
        n += self.dense * 2 + 2
        return n

    @property
    def size_bytes(self) -> float:
        return self.param_count * 4.0


def init_operator(arch: OperatorArch, key) -> dict:
    ks = jax.random.split(key, arch.conv_layers + 2)
    params = {"convs": []}
    c_in, s = 3, arch.input_size
    for i in range(arch.conv_layers):
        w = jax.random.normal(ks[i], (3, 3, c_in, arch.channels)) \
            * (2.0 / (9 * c_in)) ** 0.5
        params["convs"].append({"w": w, "b": jnp.zeros((arch.channels,))})
        c_in = arch.channels
        s = max(1, (s + 1) // 2)
    feat = s * s * c_in
    params["dense"] = {
        "w": jax.random.normal(ks[-2], (feat, arch.dense)) * (2.0 / feat) ** 0.5,
        "b": jnp.zeros((arch.dense,))}
    params["head"] = {
        "w": jax.random.normal(ks[-1], (arch.dense, 2)) * (1.0 / arch.dense) ** 0.5,
        "b": jnp.zeros((2,))}
    return params


def apply_operator(params: dict, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (N, s, s, 3) float32 -> (presence_logit (N,), count (N,))."""
    h = x
    for c in params["convs"]:
        h = jax.lax.conv_general_dilated(
            h, c["w"], window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(h + c["b"])
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["dense"]["w"] + params["dense"]["b"])
    out = h @ params["head"]["w"] + params["head"]["b"]
    return out[:, 0], jax.nn.softplus(out[:, 1])


@functools.partial(jax.jit, static_argnames=("train_count",))
def _loss_fn(params, x, y_present, y_count, train_count: bool):
    logit, cnt = apply_operator(params, x)
    bce = jnp.mean(
        jnp.maximum(logit, 0) - logit * y_present +
        jnp.log1p(jnp.exp(-jnp.abs(logit))))
    if train_count:
        huber = jnp.mean(jnp.where(jnp.abs(cnt - y_count) < 2.0,
                                   0.5 * (cnt - y_count) ** 2,
                                   2.0 * jnp.abs(cnt - y_count) - 2.0))
        return bce + 0.3 * huber
    return bce


_value_and_grad = jax.jit(jax.value_and_grad(_loss_fn),
                          static_argnames=("train_count",))


def _adam_step_body(params, m, v, xb, bright, ypb, ycb, bc1, bc2, decay, lr,
                    train_count: bool):
    """One fused train step: brightness augment, value_and_grad, Adam.

    A single jit dispatch per step — the previous eager tree_maps cost
    ~100 tiny dispatches per step, which dominated training wall-clock
    on CPU hosts. Scalar schedule terms (bc1, bc2, decay, lr) are
    computed host-side in float64 and passed as traced f32 scalars.
    The math is the same update as the historical eager loop; fusing it
    into one XLA program lets the compiler contract multiply-adds, so
    trained weights can differ from the eager loop at the last-ulp
    level. Determinism (same seed, same result) and every cross-path
    bit-identity invariant are unaffected: all training goes through
    this one step function."""
    xb = jnp.clip(xb * bright, 0.0, 1.0)
    _, g = _value_and_grad(params, xb, ypb, ycb, train_count)
    m = jax.tree_util.tree_map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ ** 2,
                               v, g)
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: decay * p -
        lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8),
        params, m, v)
    return params, m, v


@functools.cache
def _adam_step():
    """The jitted ``_adam_step_body``, built on first use. m/v (Adam
    state) and xb are produced fresh every step, so their buffers can be
    donated where XLA honours it; params must NOT be donated — train is
    resumable and callers may still be scoring with the incoming params
    (e.g. an executor running the old operator while its upgrade
    trains). Whether donation is honoured depends on the backend, and
    asking at import time would initialise it in every importer."""
    donate = (1, 2, 3) if _kops.donation_supported() else ()
    return jax.jit(_adam_step_body, static_argnames=("train_count",),
                   donate_argnums=donate)


def _draw_minibatches(rng, labels, batch: int, steps: int):
    """Every step's minibatch indices ``(steps, b)`` and brightness
    ``(steps, b, 1, 1, 1)``, ``b = min(batch, n)`` for ``n`` samples
    (one label each). Drawn from one
    stream, step after step (the positives, then the negatives, or
    plain indices when a class is missing; then the brightness), so a
    seed trains to the same parameters however the steps are
    dispatched: do not vectorise across steps."""
    n = len(labels)
    b = min(batch, n)
    # balanced minibatches: surveillance positives are rare (<10%); plain
    # sampling collapses the scorer to "always negative"
    lab = np.asarray(labels) > 0.5
    pos_idx = np.nonzero(lab)[0]
    neg_idx = np.nonzero(~lab)[0]
    balanced = len(pos_idx) > 0 and len(neg_idx) > 0
    idx = np.empty((steps, b), np.int32)
    bright = np.empty((steps, b, 1, 1, 1), np.float32)
    for t in range(steps):
        if balanced:
            half = b // 2
            idx[t, :half] = rng.choice(pos_idx, half, replace=True)
            idx[t, half:] = rng.choice(neg_idx, b - half, replace=True)
        else:
            idx[t] = rng.integers(0, n, size=b)
        # brightness augmentation: the scene dims over the day;
        # operators must generalize across capture hours
        bright[t] = rng.uniform(0.7, 1.3, (b, 1, 1, 1))
    return idx, bright


@jax.jit
def _gather_step(x, yp, yc, idx, bright, sched, t):
    """Step ``t``'s inputs from the call's uploaded draws, in one
    dispatch: its crops, presence, counts and brightness, and its bias
    corrections (bc1, bc2). Nothing is donated; the inputs serve every
    step of the call."""
    sel = idx[t]
    return x[sel], yp[sel], yc[sel], bright[t], sched[t, 0], sched[t, 1]


def train_operator(arch: OperatorArch, params: Optional[dict], crops,
                   labels, counts, *, steps: int = 120, batch: int = 128,
                   lr: float = 2e-3, seed: int = 0,
                   train_count: bool = True) -> dict:
    """Adam fine-tune on (crops, labels, counts); resumable (online
    training keeps improving the same operator as more samples arrive).

    Every step's draws and schedule terms go to the device once, before
    the first step; a step is then two dispatches, its gather and
    ``_adam_step``, whose only host input is the step's number."""
    # wall-clock scaling for expensive ops (simulated time charged apart)
    batch = int(np.clip(batch * 8e7 / max(arch.flops, 1), 32, batch))
    idx, bright = _draw_minibatches(np.random.default_rng(seed), labels,
                                    batch, steps)
    # Adam's bias corrections, computed in float64 and passed as f32
    sched = np.array([(1 - 0.9 ** t, 1 - 0.999 ** t)
                      for t in range(1, steps + 1)], np.float32)
    wd = 1e-4
    with obs.span(obs.TRAIN_UPLOAD):
        x = jnp.asarray(crops, jnp.float32)
        yp = jnp.asarray(labels, jnp.float32)
        yc = jnp.asarray(counts, jnp.float32)
        idx, bright, sched = map(jnp.asarray, (idx, bright, sched))
        decay = jnp.asarray(np.float32(1 - lr * wd))
        lr32 = jnp.asarray(np.float32(lr))
    with obs.span(obs.TRAIN_INIT):
        if params is None:
            params = init_operator(arch, jax.random.PRNGKey(seed))
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
    for t in range(steps):
        with obs.span(obs.TRAIN_STEP):
            with obs.span(obs.TRAIN_GATHER):
                xb, ypb, ycb, bright_t, bc1, bc2 = _gather_step(
                    x, yp, yc, idx, bright, sched, np.int32(t))
            with obs.span(obs.TRAIN_DISPATCH):
                params, m, v = _adam_step()(
                    params, m, v, xb, bright_t, ypb, ycb, bc1, bc2, decay,
                    lr32, train_count)
    return params


def score_frames(params: dict, crops) -> Tuple[np.ndarray, np.ndarray]:
    """Unjitted reference scoring -> (presence_prob, count) as numpy.

    Executors must NOT call this in per-chunk loops — use
    ``core/runtime.OperatorRuntime`` (cached jit, backend dispatch)."""
    logit, cnt = apply_operator(params, jnp.asarray(crops, jnp.float32))
    return np.asarray(jax.nn.sigmoid(logit)), np.asarray(cnt)


def calibrate_thresholds(scores: np.ndarray, labels: np.ndarray,
                         err: float = 0.01) -> Tuple[float, float]:
    """(lo, hi) for filters: score<lo => N, score>hi => P, else unresolved,
    s.t. estimated FN and FP rates are <= err (§6.2)."""
    scores = np.asarray(scores, np.float64)
    labels = np.asarray(labels, bool)
    order = np.argsort(scores, kind="stable")
    s, lab = scores[order], labels[order]
    n_pos = max(lab.sum(), 1)
    n_neg = max((~lab).sum(), 1)
    # lo: largest cut with cumulative positives below <= err * n_pos
    cum_pos = np.cumsum(lab)
    k = int(np.searchsorted(cum_pos, err * n_pos, side="right"))
    lo = s[k - 1] + 1e-9 if k > 0 else 0.0
    # hi: smallest cut with negatives above <= err * n_neg
    cum_neg_above = np.cumsum((~lab)[::-1])[::-1]
    ks = np.nonzero(cum_neg_above <= err * n_neg)[0]
    hi = s[ks[0]] - 1e-9 if len(ks) else 1.0
    if hi < lo:
        lo = hi
    return float(lo), float(hi)


def gamma_of(scores: np.ndarray, lo: float, hi: float) -> float:
    """Resolvable fraction under thresholds — the gamma_op of §6.2."""
    s = np.asarray(scores)
    return float(np.mean((s < lo) | (s > hi))) if len(s) else 0.0
