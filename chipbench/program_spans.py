"""The program's own spans in a traced window, beside what ``run.py``
reports.

    python3 chipbench/program_spans.py --workload <cell> --seed <n> \
        --seconds <s>

runs ``run.py`` with ``--trace 1`` and, before it deletes the trace,
reduces the spans the program opens itself (``diva.*``, named in
``src/repro/obs.py``): per name their self time, count and total time,
and the first device's idle gaps labelled by the innermost program
span. It logs them as ``[trace] program_*`` lines, with the readings of
the four per-layer metrics they define (``readings``), before the
result line, which is ``run.py``'s own. A trace without program spans
gives empty tables and no readings.
"""
from __future__ import annotations

import bisect
import collections
import sys
from typing import Dict, Sequence, Tuple

import xtrace

PREFIX = "diva."
OUTSIDE = "none"       # the label of idle time in no program span

Span = Tuple[float, float, str, str]        # start_ns, end_ns, name, thread


def summarize(spans: Sequence[Span], window: Tuple[float, float],
              busy: Sequence[Tuple[float, float]]) -> dict:
    """Self time, count and total time per span name (seconds), and the
    idle gaps between ``busy`` intervals (merged, in ns) labelled by the
    innermost span covering their midpoint, over ``window``. Spans nest
    on the thread that opened them."""
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
        s, pc = xtrace._self_times(thread)
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


def reduce(path: str) -> dict:
    """``summarize`` of the program's spans in the trace at ``path``,
    over the harness's ``window``, against the first device's busy time
    (none on a trace without a device plane)."""
    from jax.profiler import ProfileData

    window, spans, busy = None, [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == xtrace.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xtrace.WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name,
                                      line.name))
        elif busy is None and xtrace.DEVICE.fullmatch(plane.name):
            ops = xtrace._ops_line(list(plane.lines))
            if ops is not None:
                busy = [(ev.start_ns, ev.end_ns) for ev in ops.events]
    if window is None:
        raise ValueError(f"no {xtrace.WINDOW!r} span in {path}")
    busy = [(max(a, window[0]), min(b, window[1])) for a, b in busy or []]
    return summarize(spans, window,
                     xtrace.union([iv for iv in busy if iv[1] > iv[0]]))


def readings(p: dict, window_s: float) -> dict:
    """The four per-layer metrics the program's spans define, each None
    where its spans are missing:

      * ``adam_step_host_ms``: host time of one Adam iteration (draw,
        gathers, dispatch), total over count of ``diva.train.step``;
      * ``train_setup_ms``: per training call, the self time of
        ``diva.train``, ``.init``, ``.upload`` and ``.validate`` (set-up
        and validation, less crops, steps and scoring);
      * ``crop_share``: self time of ``diva.frames.crop`` over the
        window, in %;
      * ``score_dispatch_ms``: host time of one scoring dispatch
        (placement and the jit call), total over count of
        ``diva.score.dispatch``.
    """
    s, n, t = p["self_s"], p["counts"], p["total_s"]

    def per(num, den, scale):
        return scale * num / den if den else None

    setup = sum(s.get(k, 0.0) for k in ("diva.train", "diva.train.init",
                                        "diva.train.upload",
                                        "diva.train.validate"))
    crop = "diva.frames.crop"
    return {
        "adam_step_host_ms": per(t.get("diva.train.step", 0.0),
                                 n.get("diva.train.step", 0), 1e3),
        "train_setup_ms": per(setup, n.get("diva.train", 0), 1e3),
        "crop_share": per(s[crop], window_s, 100.0) if crop in s else None,
        "score_dispatch_ms": per(t.get("diva.score.dispatch", 0.0),
                                 n.get("diva.score.dispatch", 0), 1e3),
    }


def main(argv=None) -> int:
    import run

    reduce_harness = xtrace.reduce

    def reduce_both(path, *args, **kw):
        red = reduce_harness(path, *args, **kw)
        p = reduce(path)
        run.log(f"[trace] program_self_s={p['self_s']}")
        run.log(f"[trace] program_counts={p['counts']}")
        run.log(f"[trace] program_total_s={p['total_s']}")
        run.log(f"[trace] program_idle_by={p['idle_by']}")
        run.log(f"[trace] program_idle_gaps={p['idle_gaps']}")
        run.log(f"[trace] program_metrics={readings(p, red['window_s'])}")
        return red

    xtrace.reduce = reduce_both
    return run.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
