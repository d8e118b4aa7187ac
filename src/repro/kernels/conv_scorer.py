"""Pallas TPU conv scorer: the ZC² on-camera operator hot spot (§7).

The paper accelerates its AlexNet-variant operators with NNPACK on Arm;
the TPU-native analogue is a fused 3x3/stride-2 conv + bias + ReLU.

Layout. Operator channels are narrow (3 on the first layer, 8-32
after), so channels-last blocks would fill a quarter of a 128-lane
vector register at best and pad every activation 4-43x in VMEM and HBM.
The kernel instead puts the *batch* on the lanes: activations travel as
``(H, W, C, N)``, each frame is one lane, channels sit on sublanes and
the spatial axes are untiled leading dimensions.

Stride. The wrapper splits the SAME-padded input into its four stride-2
phases and stacks them on the channel axis (space-to-depth), which
turns the 3x3/stride-2 conv into a 2x2/stride-1 conv over ``4 * Cin``
channels (the fourth row/column of taps has zero weights). Every tap
inside the kernel is then a unit-stride index on a leading dimension:
no strided vector slice (Mosaic rejects those) and no relayout.

Grid. One program per (frame block, output row): it reads phase rows
``i`` and ``i + 1`` (two BlockSpecs over the same array) and, for each
output column, accumulates four ``(Cout, 4Cin) @ (4Cin, block_n)`` MXU
matmuls in f32 at HIGHEST precision. Blocks are a few MB at most, well
inside the default scoped VMEM, and the inter-layer transposes cancel
when the runtime jits a whole conv stack.

This is the scoring backend on TPU hosts (``core/runtime``); the jnp
reference in ``kernels/ref`` is what CPU hosts run and what the kernel
is tested against (interpret mode on CPU, compile tests for a described
v5e in ``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _conv_kernel(row_ref, next_ref, w_ref, b_ref, o_ref):
    # row_ref/next_ref: (1, Ws, 4Cin, Nb) phase rows i and i+1
    # w_ref: (4, Cout, 4Cin) taps (dh, dw); b_ref: (Cout, 1)
    # o_ref: (1, Wo, Cout, Nb) output row i
    rows = (row_ref, next_ref)

    def column(j, carry):
        acc = b_ref[...] + jnp.zeros(o_ref.shape[2:], jnp.float32)
        for dh in range(2):
            for dw in range(2):
                acc += jnp.dot(w_ref[2 * dh + dw], rows[dh][0, j + dw],
                               preferred_element_type=jnp.float32,
                               precision=_HIGHEST)
        o_ref[0, j] = jnp.maximum(acc, 0.0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1], column, 0)


@functools.partial(jax.jit, static_argnames=("stride", "block_n", "interpret"))
def conv_scorer(x, w, b, *, stride: int = 2, block_n: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """Fused 3x3 SAME conv + bias + ReLU. x: (N, H, W, Cin) -> (N, Ho, Wo, Cout).

    ``block_n`` frames share one program (the lane width of every
    operand); batches up to ``block_n`` run as a single block."""
    if stride != 2:
        raise ValueError(f"conv_scorer implements stride 2 only, got {stride}")
    N, H, W, Cin = x.shape
    Cout = w.shape[-1]
    Ho, Wo = -(-H // 2), -(-W // 2)
    Hs, Ws = Ho + 1, Wo + 1              # phase-grid extent (one halo row/col)
    K = 4 * Cin
    block_n = N if N <= block_n else block_n
    padn = (-N) % block_n
    # SAME padding for 3x3/stride 2 (XLA's split: extra pixel at the end),
    # extended to an even 2*Hs x 2*Ws so the phases tile exactly
    top = ((Ho - 1) * 2 + 3 - H) // 2
    left = ((Wo - 1) * 2 + 3 - W) // 2
    xp = jnp.pad(x.transpose(1, 2, 3, 0),
                 ((top, 2 * Hs - H - top), (left, 2 * Ws - W - left),
                  (0, 0), (0, padn)))
    # phase (a, b) holds padded pixels (2p + a, 2q + b): (Hs, Ws, 4Cin, Np)
    z = jnp.concatenate([xp[a::2, c::2] for a in (0, 1) for c in (0, 1)],
                        axis=2)
    # 3x3 taps -> 2x2 taps over phases: tap (kh, kw) = (2dh + a, 2dw + c);
    # taps with kh or kw == 3 do not exist and get zero weights
    w4 = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    w4 = w4.reshape(2, 2, 2, 2, Cin, Cout).transpose(0, 2, 5, 1, 3, 4)
    w4 = w4.reshape(4, Cout, K)
    Np = N + padn
    out = pl.pallas_call(
        _conv_kernel,
        grid=(Np // block_n, Ho),
        in_specs=[
            pl.BlockSpec((1, Ws, K, block_n), lambda n, i: (i, 0, 0, n)),
            pl.BlockSpec((1, Ws, K, block_n), lambda n, i: (i + 1, 0, 0, n)),
            pl.BlockSpec((4, Cout, K), lambda n, i: (0, 0, 0)),
            pl.BlockSpec((Cout, 1), lambda n, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Wo, Cout, block_n),
                               lambda n, i: (i, 0, 0, n)),
        out_shape=jax.ShapeDtypeStruct((Ho, Wo, Cout, Np), x.dtype),
        interpret=interpret,
    )(z, z, w4, b.reshape(Cout, 1))
    return out[..., :N].transpose(3, 0, 1, 2)
