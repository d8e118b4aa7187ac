"""``train_operator`` draws a call's minibatches up front and gathers
each step's in one jitted dispatch. Against the per-step loop it
replaced (kept here as the reference): the same samples, the same
brightness, bitwise the same trained parameters, and ``_adam_step``
called once a step with the gathered minibatch, as the chip
benchmark's probes expect."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators
from repro.core.operators import OperatorArch, init_operator, train_operator

ARCH = OperatorArch("gather_L2c8d16s25", 2, 8, 16, 25)
BATCH = 32


def _data(n, labels="balanced", seed=0):
    rng = np.random.default_rng(seed)
    crops = rng.uniform(size=(n, 25, 25, 3)).astype(np.float32)
    if labels == "balanced":
        lab = (np.arange(n) % 3 == 0).astype(np.float32)
    else:
        lab = np.zeros(n, np.float32)
    counts = (np.arange(n) % 4).astype(np.float32)
    return crops, lab, counts


def _reference_draws(labels, n, batch, steps, seed):
    """The per-step draws of the old loop: (sel, bright) for each step."""
    rng = np.random.default_rng(seed)
    lab = np.asarray(labels) > 0.5
    pos_idx = np.nonzero(lab)[0]
    neg_idx = np.nonzero(~lab)[0]
    balanced = len(pos_idx) > 0 and len(neg_idx) > 0
    out = []
    for _ in range(steps):
        if balanced:
            half = min(batch, n) // 2
            sel = np.concatenate([
                rng.choice(pos_idx, half, replace=True),
                rng.choice(neg_idx, min(batch, n) - half, replace=True)])
        else:
            sel = rng.integers(0, n, size=min(batch, n))
        bright = np.asarray(rng.uniform(0.7, 1.3, (len(sel), 1, 1, 1)),
                            np.float32)
        out.append((sel, bright))
    return out


def _reference_train(arch, params, crops, labels, counts, *, steps, batch,
                     lr=2e-3, seed=0, train_count=True):
    """The old loop: three eager gathers a step, then ``_adam_step``."""
    x = jnp.asarray(crops, jnp.float32)
    yp = jnp.asarray(labels, jnp.float32)
    yc = jnp.asarray(counts, jnp.float32)
    if params is None:
        params = init_operator(arch, jax.random.PRNGKey(seed))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    batch = int(np.clip(batch * 8e7 / max(arch.flops, 1), 32, batch))
    decay = np.float32(1 - lr * 1e-4)
    lr32 = np.float32(lr)
    draws = _reference_draws(labels, x.shape[0], batch, steps, seed)
    for t, (sel, bright) in enumerate(draws, start=1):
        xb, ypb, ycb = x[sel], yp[sel], yc[sel]
        params, m, v = operators._adam_step()(
            params, m, v, xb, bright, ypb, ycb,
            np.float32(1 - 0.9 ** t), np.float32(1 - 0.999 ** t),
            decay, lr32, train_count)
    return params


def _assert_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for p, q in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


@pytest.mark.parametrize("n,labels,train_count", [
    (80, "balanced", True),         # balanced draws, n > batch
    (80, "one_class", True),        # one class: rng.integers
    (20, "balanced", True),         # n < batch
    (20, "one_class", False),       # n < batch, presence only
])
def test_trained_params_bitwise_as_old_loop(n, labels, train_count):
    crops, lab, counts = _data(n, labels)
    kw = dict(steps=5, batch=BATCH, seed=7, train_count=train_count)
    _assert_bitwise(train_operator(ARCH, None, crops, lab, counts, **kw),
                    _reference_train(ARCH, None, crops, lab, counts, **kw))


def test_resumed_call_bitwise_as_old_loop():
    crops, lab, counts = _data(60)
    start = train_operator(ARCH, None, crops, lab, counts, steps=3,
                           batch=BATCH, seed=1)
    more, lab2, counts2 = _data(72, seed=1)
    kw = dict(steps=4, batch=BATCH, seed=2)
    _assert_bitwise(train_operator(ARCH, start, more, lab2, counts2, **kw),
                    _reference_train(ARCH, start, more, lab2, counts2, **kw))


@pytest.mark.parametrize("n", [80, 20])
def test_adam_step_receives_gathered_minibatch(monkeypatch, n):
    """Wrapping the ``_adam_step`` factory as the benchmark's probes do:
    one call a step, ``xb`` of ``(min(batch, n), s, s, 3)`` whose rows
    are byte for byte the drawn crops (brightness is applied inside the
    step), ``bright`` that ``np.array`` turns into the step's draw, the
    drawn labels and counts, and the step's schedule terms (bc1, bc2,
    decay, lr)."""
    crops, lab, counts = _data(n)
    steps, seed = 4, 3
    seen = []
    factory = operators._adam_step

    @functools.cache
    def counted_factory():
        step = factory()

        def counted(params, m, v, xb, bright, *rest, **kw):
            # xb may be donated to the step: copy it first
            ypb, ycb, *sched = rest[:6]
            seen.append((np.array(xb), np.array(bright), np.array(ypb),
                         np.array(ycb), [float(np.array(r)) for r in sched]))
            return step(params, m, v, xb, bright, *rest, **kw)
        return counted

    monkeypatch.setattr(operators, "_adam_step", counted_factory)
    train_operator(ARCH, None, crops, lab, counts, steps=steps, batch=BATCH,
                   seed=seed)
    draws = _reference_draws(lab, n, BATCH, steps, seed)
    assert len(seen) == steps
    rows = {r.tobytes() for r in crops}
    for t, ((xb, bright, ypb, ycb, sched), (sel, want_bright)) in enumerate(
            zip(seen, draws), start=1):
        np.testing.assert_array_equal(ypb, lab[sel])
        np.testing.assert_array_equal(ycb, counts[sel])
        assert sched == [np.float32(1 - 0.9 ** t), np.float32(1 - 0.999 ** t),
                         np.float32(1 - 2e-3 * 1e-4), np.float32(2e-3)]
        assert xb.shape == (min(BATCH, n), 25, 25, 3)
        assert xb.dtype == np.float32
        assert all(r.tobytes() in rows for r in xb)
        np.testing.assert_array_equal(xb, crops[sel])
        assert bright.dtype == np.float32
        np.testing.assert_array_equal(bright, want_bright)


def test_draws_match_old_loop_order():
    """All of a call's draws, made before its first step, are the old
    loop's step by step: the stream is not reordered across steps."""
    for labels in ("balanced", "one_class"):
        _, lab, _ = _data(50, labels)
        idx, bright = operators._draw_minibatches(
            np.random.default_rng(11), lab, BATCH, 6)
        assert idx.shape == (6, BATCH) and idx.dtype == np.int32
        assert bright.shape == (6, BATCH, 1, 1, 1)
        for t, (sel, b) in enumerate(_reference_draws(lab, 50, BATCH, 6,
                                                      11)):
            np.testing.assert_array_equal(idx[t], sel)
            np.testing.assert_array_equal(bright[t], b)
