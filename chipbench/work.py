"""Operations and bytes of the operator family, counted from shapes.

The yardstick for ``conv_scorer_roofline`` and ``mfu``. It counts the
work the mathematics asks for, whatever implements it: the stride-2
3x3 SAME conv stack that ``kernels/ref`` computes (only taps that land
inside the image, as XLA's ``cost_analysis`` counts them), with bias
and ReLU, then the dense layer and the two-output head. A signature is
``(conv_layers, channels, dense, input_size)``; inputs have 3 channels.
Not the camera's cost model (``OperatorArch.flops``: stride-1 conv plus
pooling) and not the taps today's kernel runs over phases.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"

Sig = Tuple[int, int, int, int]


def _valid_taps(size: int) -> Tuple[int, int]:
    """(output size, taps inside the image summed over output positions)
    along one axis of a 3-tap, stride-2 SAME window."""
    out = -(-size // 2)
    pad_lo = max((out - 1) * 2 + 3 - size, 0) // 2
    taps = 0
    for i in range(out):
        start = 2 * i - pad_lo
        taps += sum(1 for k in range(3) if 0 <= start + k < size)
    return out, taps


def conv_layers(sig: Sig):
    """Per conv layer: (flops, bytes) of one frame. Bytes are the
    layer's input read and output written, in f32."""
    layers, channels, _dense, size = sig
    s, c_in, out = size, 3, []
    for _ in range(layers):
        o, taps = _valid_taps(s)
        flops = 2.0 * taps * taps * c_in * channels + 2.0 * o * o * channels
        out.append((flops, F32 * (s * s * c_in + o * o * channels)))
        s, c_in = o, channels
    return out


def feature_size(sig: Sig) -> int:
    layers, channels, _dense, s = sig
    for _ in range(layers):
        s = -(-s // 2)
    return s * s * channels


def conv_flops(sig: Sig) -> float:
    return sum(f for f, _ in conv_layers(sig))


def conv_bytes(sig: Sig) -> float:
    return sum(b for _, b in conv_layers(sig))


def forward_flops(sig: Sig) -> float:
    """One frame through the whole operator: convs, dense, head."""
    dense = sig[2]
    feat = feature_size(sig)
    return (conv_flops(sig) + 2.0 * feat * dense + 2.0 * dense
            + 2.0 * dense * 2 + 2)


def train_flops(sig: Sig) -> float:
    """One sample of one Adam step: forward and backward, taken as three
    forward passes (the backward pass computes two products per one of
    the forward)."""
    return 3.0 * forward_flops(sig)


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: known {sorted(table)}")
    return table[device_kind]


def conv_min_seconds(frames: Dict[Sig, int], peak: Dict[str, float]
                     ) -> Tuple[float, str]:
    """The least time the chip could take for the conv layers of
    ``frames[sig]`` real frames per signature, and which bound set it
    ("flops" or "bytes", by the larger share of the total)."""
    t_f = t_b = total = 0.0
    for sig, n in frames.items():
        f = n * conv_flops(sig) / peak["flops_per_s"]
        b = n * conv_bytes(sig) / peak["bytes_per_s"]
        total += max(f, b)
        t_f += f if f >= b else 0.0
        t_b += b if b > f else 0.0
    return total, ("flops" if t_f > t_b else "bytes")
