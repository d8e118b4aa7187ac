"""``work.py`` counts what XLA counts for the ``kernels/ref`` stack, and
the peaks table refuses a device it does not know."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "chipbench"), str(ROOT / "src")]

import work  # noqa: E402
from repro.kernels import ref  # noqa: E402


def _stack(convs, dense, head, x):
    h = x
    for w, b in convs:
        h = ref.conv_scorer(h, w, b, 2)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ dense[0] + dense[1])
    return h @ head[0] + head[1]


@pytest.mark.parametrize("sig", [(2, 8, 16, 25), (4, 16, 32, 50),
                                 (5, 32, 64, 100)])
def test_forward_count_matches_cost_analysis(sig):
    layers, ch, dense, size = sig
    f32 = jnp.float32
    convs, c_in = [], 3
    for _ in range(layers):
        convs.append((jax.ShapeDtypeStruct((3, 3, c_in, ch), f32),
                      jax.ShapeDtypeStruct((ch,), f32)))
        c_in = ch
    feat = work.feature_size(sig)
    dense_p = (jax.ShapeDtypeStruct((feat, dense), f32),
               jax.ShapeDtypeStruct((dense,), f32))
    head_p = (jax.ShapeDtypeStruct((dense, 2), f32),
              jax.ShapeDtypeStruct((2,), f32))
    x = jax.ShapeDtypeStruct((1, size, size, 3), f32)
    cost = jax.jit(_stack).lower(convs, dense_p, head_p, x).compile() \
        .cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert work.forward_flops(sig) == pytest.approx(cost["flops"], rel=1e-3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v0 imaginary")
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    least, bound = work.conv_min_seconds({(2, 8, 16, 25): 1000}, peak)
    assert bound == "bytes"
    assert least == pytest.approx(1000 * work.conv_bytes((2, 8, 16, 25))
                                  / 819e9)
