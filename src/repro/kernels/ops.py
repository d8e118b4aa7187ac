"""Jit'd dispatch wrappers for the Pallas kernels.

``use_pallas(True)`` flips the model's hot paths onto the kernels (TPU);
the default keeps the pure-jnp/XLA paths (CPU dry-run and tests compare
both). CPU tests run the kernels with interpret=True; on a TPU they
are compiled (``tests/test_tpu_compile.py`` compiles them for a
described v5e).

``conv_scorer_fn`` resolves the conv backend *once* and returns a
callable with the choice baked in — callers that jit-compile (the
operator scoring runtime) need a decision that is static per compiled
function, not read from mutable context-manager state at trace time.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax

from repro.kernels import (conv_scorer as _conv, decode_attention as _dec,
                           flash_attention as _fa, moe_gmm as _gmm,
                           rmsnorm as _rms, ref)

_STATE = {"pallas": False, "interpret": False}


@contextlib.contextmanager
def use_pallas(enabled: bool = True, interpret: bool = False):
    prev = dict(_STATE)
    _STATE.update(pallas=enabled, interpret=interpret)
    try:
        yield
    finally:
        _STATE.update(prev)


def enabled() -> bool:
    return _STATE["pallas"]


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None):
    if _STATE["pallas"]:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=_STATE["interpret"])
    return ref.attention(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v):
    if _STATE["pallas"]:
        return _dec.decode_attention(q, k, v,
                                     interpret=_STATE["interpret"])
    return ref.decode_attention(q, k, v)


def rmsnorm(x, scale, eps: float = 1e-6):
    if _STATE["pallas"]:
        return _rms.rmsnorm(x, scale, eps=eps,
                            interpret=_STATE["interpret"])
    return ref.rmsnorm(x, scale, eps)


def moe_gmm(x, w):
    if _STATE["pallas"]:
        return _gmm.moe_gmm(x, w, interpret=_STATE["interpret"])
    return ref.moe_gmm(x, w)


def conv_scorer(x, w, b, *, stride: int = 2):
    if _STATE["pallas"]:
        return _conv.conv_scorer(x, w, b, stride=stride,
                                 interpret=_STATE["interpret"])
    return ref.conv_scorer(x, w, b, stride)


def default_conv_backend() -> str:
    """Pallas on TPU hosts, the jnp reference everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def donation_supported() -> bool:
    """Whether ``donate_argnums`` buffer donation is honored on this
    host. XLA:CPU ignores donation and warns per dispatch, so the
    scoring runtime only donates its input buffers off-CPU."""
    return jax.default_backend() != "cpu"


def conv_scorer_fn(backend: Optional[str] = None, *, stride: int = 2,
                   interpret: bool = False) -> Callable:
    """Resolve the conv-scorer backend to a concrete callable.

    Unlike ``conv_scorer`` above, the returned function does not consult
    ``_STATE`` — the backend is fixed at resolution time, so it is safe
    to close over inside a jit-compiled scoring function.
    """
    backend = backend or default_conv_backend()
    if backend == "pallas":
        return functools.partial(_conv.conv_scorer, stride=stride,
                                 interpret=interpret)
    if backend == "jnp":
        return functools.partial(ref.conv_scorer, stride=stride)
    raise ValueError(f"unknown conv backend: {backend!r}")
