"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and
idle time, the kernel's time, the device time of each jitted program,
the self time of the harness's host spans and of the program's own
(``diva.*``, named in ``src/repro/obs.py``), and the idle gaps labelled
by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. The measured window is
the harness's ``window`` annotation on the host. Device operations are
the events of the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane; a
device is busy while any of them runs (the union of their intervals).
The programs are the events of its ``XLA Modules`` line, named
``jit_<function>(<hash>)``. A span's self time is its length less the
part its child spans cover; spans nest on the thread that opened them.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "window"
HOST_PLANE = "/host:CPU"
DEVICE = re.compile(r"/device:[A-Z_]+:\d+")   # one plane per chip
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PROGRAM = "diva."          # the prefix of the program's own span names
OUTSIDE = "none"           # the label of idle time in no program span
OP_CHARS = 160     # of an op's whole instruction, in the breakdown

Interval = Tuple[float, float]


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _self_times(spans: List[Tuple[float, float, str]]
                ) -> Tuple[Dict[str, float], List[Tuple[float, float, str]]]:
    """Self time per name of properly nested spans of one thread, and
    the spans as leaves: pieces of each span not covered by a child."""
    selft: Dict[str, float] = collections.defaultdict(float)
    pieces: List[Tuple[float, float, str]] = []
    stack: List[list] = []          # [start, end, name, cursor]

    def close(top):
        if top[3] < top[1]:
            pieces.append((top[3], top[1], top[2]))
        selft[top[2]] += 0.0      # listed even if children cover it all

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            b = min(b, parent[1])
            if parent[3] < a:
                pieces.append((parent[3], a, parent[2]))
            parent[3] = b
        stack.append([a, b, name, a])
    while stack:
        close(stack.pop())
    for a, b, name in pieces:
        selft[name] += b - a
    return dict(selft), pieces


def summarize(spans: Sequence[Tuple[float, float, str, str]],
              window: Interval, busy: Sequence[Interval]) -> dict:
    """Self time, count and total time per span name (seconds) of
    ``spans`` (start and end in ns, name, thread), and the idle gaps
    between ``busy`` intervals (merged, in ns) labelled by the innermost
    span covering their midpoint, over ``window``. Spans nest on the
    thread that opened them."""
    w0, w1 = window
    by_thread: Dict[str, list] = collections.defaultdict(list)
    counts: Dict[str, int] = collections.Counter()
    total: Dict[str, float] = collections.defaultdict(float)
    for a, b, name, line in spans:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            by_thread[line].append((a, b, name))
            counts[name] += 1
            total[name] += (b - a) * 1e-9
    selft: Dict[str, float] = collections.defaultdict(float)
    threads = []        # per thread: its pieces, sorted, and their starts
    for thread in by_thread.values():
        s, pc = _self_times(thread)
        for k, v in s.items():
            selft[k] += v * 1e-9
        pc.sort()
        threads.append((pc, [piece[0] for piece in pc]))

    def label(t):
        """The innermost span at ``t``: of the threads in a span then,
        the one whose piece started last."""
        best = None
        for pc, starts in threads:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and pc[i][0] <= t < pc[i][1] and (
                    best is None or pc[i][0] > best[0]):
                best = pc[i]
        return OUTSIDE if best is None else best[2]

    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(label((a + b) / 2), (b - a) * 1e-9)
            for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -g[1])
    idle_by: Dict[str, float] = collections.defaultdict(float)
    for name, s in gaps:
        idle_by[name] += s
    return {"self_s": dict(selft), "counts": dict(counts),
            "total_s": dict(total), "idle_by": dict(idle_by),
            "idle_gaps": [[k, s] for k, s in gaps[:10]]}


def _ops_line(lines):
    """The line of a device plane that holds its operations: ``XLA Ops``,
    else the busiest line."""
    for line in lines:
        if line.name == OPS_LINE:
            return line
    return max(lines, key=lambda ln: len(list(ln.events)), default=None)


def op_name(text: str) -> str:
    """The HLO instruction's own name (``%conv_scorer.2``) of a device
    event, whose name is the whole instruction: ``%name = shape op(...)``."""
    return text.split(" = ", 1)[0]


def module_name(text: str) -> str:
    """A jitted program's name without its hash: ``jit_scorer`` of
    ``jit_scorer(3975087741005274656)``."""
    return re.sub(r"\(\d+\)$", "", text)


def _is_kernel(ev, kernel: str) -> bool:
    """An event is the kernel's when its instruction's own name holds
    the kernel's: a Pallas call is the custom call named for it
    (``%conv_scorer.2 = ... custom-call(...)``). The operands are left
    out, so an op that reads the kernel's output is not the kernel's."""
    return kernel in op_name(ev.name)


def reduce(path: str, span_names: Sequence[str], kernel: str,
           outside: str = "harness", inside_run: Tuple[str, str] = ("run",
                                                                   "loop")
           ) -> dict:
    """Everything the per-layer readers need from one trace.

    ``kernel``: substring of the instruction names of the kernel's
    device events. Device operations are summed by their whole
    instruction (one per program and shape) and listed by its first
    ``OP_CHARS`` characters; ``module_s`` sums them by jitted program
    instead (``module_name``). Both, like ``kernel_s``, are averaged
    over the devices.
    Gaps are labelled by the innermost span covering their midpoint;
    the ``run`` span's own time is labelled ``loop`` and time in no
    span ``harness``. ``program`` is ``summarize`` of the program's own
    spans against the first device's busy time. Times are in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: List[Tuple[float, float, str, str]] = []
    window: Optional[Interval] = None
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    modules: List[Tuple[float, float, str]] = []
    program: List[Tuple[float, float, str, str]] = []
    names = set(span_names)
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines][:12]
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in names:
                        host.append((ev.start_ns, ev.end_ns, ev.name,
                                     line.name))
                    elif ev.name.startswith(PROGRAM):
                        program.append((ev.start_ns, ev.end_ns, ev.name,
                                        line.name))
        elif DEVICE.fullmatch(plane.name):
            plane_lines = list(plane.lines)
            modules += [(ev.start_ns, ev.end_ns, module_name(ev.name))
                        for ln in plane_lines if ln.name == MODULES_LINE
                        for ev in ln.events]
            ops = _ops_line(plane_lines)
            if ops is not None:
                devices[plane.name] = [
                    (ev.start_ns, ev.end_ns,
                     kernel if _is_kernel(ev, kernel) else ev.name)
                    for ev in ops.events]
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    w0, w1 = window
    span_s = (w1 - w0) * 1e-9

    def clip(a, b):
        return max(a, w0), min(b, w1)

    by_thread: Dict[str, list] = collections.defaultdict(list)
    for a, b, name, line in host:
        a, b = clip(a, b)
        if b > a:
            by_thread[line].append((a, b, name))
    selft: Dict[str, float] = collections.defaultdict(float)
    pieces: List[Tuple[float, float, str]] = []
    for spans in by_thread.values():
        s, pc = _self_times(spans)
        for k, v in s.items():
            selft[k] += v
        pieces += pc

    busy, kernel_ns, ops = [], 0.0, collections.Counter()
    for evs in devices.values():
        ivs = []
        for a, b, name in evs:
            a, b = clip(a, b)
            if b <= a:
                continue
            ivs.append((a, b))
            ops[name] += b - a
            if name == kernel:
                kernel_ns += b - a
        busy.append(union(ivs))
    n_dev = max(len(busy), 1)
    busy_ns = sum(_length(u) for u in busy) / n_dev
    module_ns = collections.Counter()
    for a, b, name in modules:
        a, b = clip(a, b)
        if b > a:
            module_ns[name] += b - a

    # idle gaps of the first device, labelled by the host's innermost span
    gaps = []
    if busy:
        edges = [w0] + [x for iv in busy[0] for x in iv] + [w1]
        pieces.sort()
        starts = [pc[0] for pc in pieces]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = outside
            if i >= 0 and pieces[i][0] <= mid < pieces[i][1]:
                label = pieces[i][2]
                if label == inside_run[0]:
                    label = inside_run[1]
            gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    idle_by = collections.Counter()
    for label, s in gaps:
        idle_by[label] += s
    return {
        "window_s": span_s,
        "busy_s": busy_ns * 1e-9,
        "devices": len(devices),
        "kernel_s": kernel_ns * 1e-9 / n_dev,
        "module_s": {k: v * 1e-9 / n_dev for k, v in module_ns.items()},
        "self_s": {k: v * 1e-9 for k, v in selft.items()},
        "device_ops": [[k[:OP_CHARS], v * 1e-9 / n_dev]
                       for k, v in ops.most_common(10)],
        "idle_gaps": [[k, s] for k, s in gaps[:10]],
        "idle_by_host": dict(idle_by),
        "lines": lines,
        "program": summarize(program, window, busy[0] if busy else []),
    }
