"""OperatorRuntime — the shared batched scoring engine (§7 fast path).

Every query executor used to carry its own 1024-chunk ``score_frames``
loop over the unjitted jnp apply, retracing the conv stack on every
call and never touching the Pallas ``kernels/conv_scorer`` kernel. This
module centralizes scoring behind three dispatch layers (see
``docs/ARCHITECTURE.md`` "Dispatch layers"):

  * **lean small-shape dispatch** — below ``small_flops`` useful FLOPs
    per dispatch, padding overhead rivals the compute itself, so the
    batch skips power-of-two bucketing entirely: a per-(signature,
    quantized-shape) jit with the input buffer donated. This is the
    fix for the sub-1x small-arch regression the ROADMAP flagged.
  * **bucketed single dispatch** — larger batches are zero-padded to
    power-of-two buckets (min 64, max ``chunk``) so compilation sees a
    handful of stable shapes instead of one per call.
  * **stacked superbatch dispatch** — ``ScoreBatcher`` fuses up to
    ``group_max`` same-(signature, bucket) chunks from *different*
    queries into one ``(group, bucket, …)`` dispatch whose scorer body
    maps the single-chunk computation over stacked per-query params
    (``jax.vmap`` on Pallas/TPU; a statically unrolled map on CPU,
    where XLA's grouped convolutions are slow). One trace per
    (signature, group size, bucket) for the entire fleet — the old
    tuple-of-args grouping retraced per distinct shape *tuple*, which
    is combinatorial in the demand mix.

All three layers run the identical ``_scorer_body`` math and padding
rows cannot perturb real rows, so every path is bit-identical to every
other (property-tested in ``tests/test_runtime.py``); schedulers are
free to choose dispatch layout purely for performance.

Executors reach the runtime through ``QuerySession.score``; the cloud
trainer's validation scoring goes through ``get_runtime().score_crops``;
the ``FleetScheduler`` feeds a ``ScoreBatcher``, which issues fused
dispatches eagerly as demands accumulate and keeps results on-device
(``ScoreHandle``) until the scheduler consumes them — JAX async
dispatch then overlaps device compute with the host-side uplink
simulation. The process-global runtime means a query fleet sharing one
host also shares one compilation cache.

On multi-device hosts the runtime adds a fourth, orthogonal dimension:
constructed with a 1-D ``("data",)`` mesh (``launch/mesh.
make_scoring_mesh``), stacked superbatches are committed with a
group-axis ``NamedSharding`` and XLA partitions the same traced scorer
body across devices — one trace per (signature, shape) still,
bitwise-identical results (each group member's computation stays whole
on one device), N-way device parallelism per fused dispatch. Group
sizes that do not divide the device count replicate instead
(``parallel/sharding`` divisibility rules, recorded and summarized by
``sharding_fallbacks()``); flat small/bucketed batches stay
single-device unless ``shard_frames=True`` explicitly opts into
frame-axis sharding, which is *not* bitwise-safe on XLA:CPU (local row
counts change gemm blocking, reassociating accumulation by ~1 ulp).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels import ops as kops
from repro.parallel import sharding as shd

ArchSig = Tuple[int, int, int, int]

_HIGHEST = jax.lax.Precision.HIGHEST

CHUNK = 1024          # frames per dispatch (bounds crop-cache pressure)
MIN_BUCKET = 64       # smallest padded batch shape (bucketed path)
# Useful FLOPs per dispatch below which the lean small-shape path runs.
# Calibrate for a host with ``benchmarks.roofline.calibrate_small_flops``
# (the default corresponds to a few ms of compute on a laptop-class
# core, where padding to a power of two costs more than it saves).
SMALL_FLOPS = 3e8
# Small-shape batches are quantized up to a multiple of this (instead of
# a power of two) purely to bound the compiled-shape vocabulary; 1
# disables quantization (exact shapes).
SMALL_QUANT = 32


def arch_signature(arch) -> ArchSig:
    """Shape-relevant part of an OperatorArch: the input region changes
    *which pixels* are cropped, not the compiled computation."""
    return (arch.conv_layers, arch.channels, arch.dense, arch.input_size)


def sig_flops(sig: ArchSig) -> float:
    """Per-frame inference FLOPs of a signature — the cost model of
    ``OperatorArch.flops`` restated over the signature fields (region
    variants share it), used to pick a dispatch layer per batch."""
    layers, channels, dense, size = sig
    s, c_in, total = size, 3, 0.0
    for _ in range(layers):
        total += 2.0 * s * s * channels * 9 * c_in
        c_in = channels
        s = max(1, (s + 1) // 2)
    total += 2.0 * (s * s * c_in) * dense + 2.0 * dense * 2
    return total


class OperatorRuntime:
    """Batched operator scoring with a per-arch jit cache.

    ``backend``: "pallas" | "jnp" | None (auto: pallas iff running on
    TPU). ``interpret`` runs Pallas kernels in interpreter mode (tests).
    ``small_flops``/``small_quant`` tune the small-shape fast path;
    ``superbatch`` picks the fused-dispatch style ("vmap" | "unroll",
    auto per backend). ``calls`` counts **jit dispatches** on every
    path (one fused superbatch = one call), so dispatch numbers are
    comparable between ``score_crops`` and ``ScoreBatcher`` scoring.

    ``mesh``: an optional 1-D ``("data",)`` mesh (see
    ``launch/mesh.make_scoring_mesh``). When it holds >1 device, every
    stacked superbatch is placed with a group-axis ``NamedSharding``
    and its scorer body runs under ``jax.shard_map`` on each device's
    members (XLA cannot partition a Pallas kernel itself). Each
    group member's full ``(bucket, …)`` computation stays whole on one
    device — exactly the single-device shapes and accumulation order —
    so sharded results are bitwise identical to single-device ones
    (asserted in ``tests/test_sharded_scoring.py``). Group sizes that
    do not divide the data axis replicate instead of crashing
    (``parallel/sharding`` divisibility rules); each such step-down is
    recorded and summarized by ``sharding_fallbacks()``. Flat
    small/bucketed batches stay on the default device: frame-axis
    partitioning shrinks the local row count, which changes XLA:CPU
    gemm blocking and reassociates accumulation (~1 ulp) — opt in with
    ``shard_frames=True`` only where that is acceptable. The sharding
    spec is a pure function of the dispatch shape, so a given
    (signature, shape) still traces exactly once — TraceGuard holds
    under sharding.
    """

    def __init__(self, *, backend: Optional[str] = None,
                 interpret: bool = False, chunk: int = CHUNK,
                 min_bucket: int = MIN_BUCKET,
                 small_flops: float = SMALL_FLOPS,
                 small_quant: int = SMALL_QUANT,
                 superbatch: Optional[str] = None,
                 mesh=None, shard_frames: bool = False):
        self.backend = backend or kops.default_conv_backend()
        if self.backend not in ("pallas", "jnp"):
            raise ValueError(f"unknown conv backend: {self.backend!r}")
        self.interpret = interpret
        self.chunk = int(chunk)
        self.min_bucket = int(min_bucket)
        self.small_flops = float(small_flops)
        self.small_quant = max(int(small_quant), 1)
        # XLA grouped convolutions (what vmap-over-params lowers to) are
        # fast on TPU but markedly slower than an unrolled member-wise
        # map on the CPU backend — pick per backend, overridable.
        self.superbatch = superbatch or (
            "vmap" if self.backend == "pallas" else "unroll")
        if self.superbatch not in ("vmap", "unroll"):
            raise ValueError(f"unknown superbatch style: {self.superbatch!r}")
        # device-parallel dispatch: shard inputs over the mesh's data
        # axis when there is more than one device to spread across
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self.device_count = mesh.size if self.mesh is not None else 1
        self.shard_frames = bool(shard_frames)
        self._fallbacks: List[tuple] = []   # (axis, dim, mapped) records
        # input batches are built fresh per dispatch, so they are safe
        # to donate; XLA only honors donation off-CPU (kops helper)
        self._donate = (1,) if kops.donation_supported() else ()
        self._apply: Dict[ArchSig, Callable] = {}                # bucketed
        self._small: Dict[Tuple[ArchSig, int], Callable] = {}    # lean
        self._super: Dict[ArchSig, Callable] = {}                # fused
        self._traces: Dict[ArchSig, int] = {}
        self._group_traces: Dict[ArchSig, int] = {}
        # (sig, shape-key) -> trace count; the invariant TraceGuard
        # asserts is that no key ever reaches 2 (shapes are bucketed/
        # quantized, so distinct keys tracing once each is expected)
        self._shape_traces: Dict[Tuple[ArchSig, tuple], int] = {}
        # sig -> dispatch-shape vocabulary actually used (bench reports
        # assert traces_per_arch <= len(vocabulary))
        self._shape_vocab: Dict[ArchSig, set] = {}
        self.calls = 0
        self.frames_scored = 0       # real (caller-requested) frames
        self.frames_padded = 0       # zero rows added for shape stability
        self.small_calls = 0
        self.bucketed_calls = 0
        self.super_calls = 0

    # -- compilation cache ---------------------------------------------------

    def apply_fn(self, arch) -> Callable:
        """The bucketed-path jit-compiled ``(params, x) -> (probs,
        counts)`` for an arch — built once per signature per runtime."""
        return self._bucket_fn(arch_signature(arch))

    def _scorer_body(self, sig: ArchSig) -> Callable:
        """The per-batch ``(params, x) -> (probs, counts)`` computation —
        shared verbatim by all three dispatch layers, so dispatch layout
        cannot change the traced math."""
        conv = kops.conv_scorer_fn(self.backend, interpret=self.interpret)

        def dense(h, layer):
            # f32 at full precision: a TPU otherwise rounds matmul
            # inputs to bf16 (XLA:CPU computes f32 either way)
            return jnp.dot(h, layer["w"], precision=_HIGHEST) + layer["b"]

        def scorer(params, x):
            h = x
            for c in params["convs"]:
                h = conv(h, c["w"], c["b"])
            h = h.reshape(h.shape[0], -1)
            out = dense(jax.nn.relu(dense(h, params["dense"])),
                        params["head"])
            return jax.nn.sigmoid(out[:, 0]), jax.nn.softplus(out[:, 1])

        return scorer

    def _record_trace(self, sig: ArchSig, shape_key: tuple,
                      *, grouped: bool = False) -> None:
        """Called from inside traced bodies — i.e. at trace time only —
        so the counters tally compilations, not dispatches."""
        if grouped:
            self._group_traces[sig] = self._group_traces.get(sig, 0) + 1
        else:
            self._traces[sig] = self._traces.get(sig, 0) + 1
        key = (sig, shape_key)
        self._shape_traces[key] = self._shape_traces.get(key, 0) + 1

    def _bucket_fn(self, sig: ArchSig) -> Callable:
        fn = self._apply.get(sig)
        if fn is None:
            body = self._scorer_body(sig)

            def scorer(params, x):
                # executes at trace time only: counts compilations
                self._record_trace(sig, tuple(x.shape))
                return body(params, x)

            fn = jax.jit(scorer, donate_argnums=self._donate)
            self._apply[sig] = fn
        return fn

    def _small_fn(self, sig: ArchSig, n: int) -> Callable:
        """The lean small-shape dispatch: no bucketing, one compiled
        function per (signature, quantized batch size), input donated."""
        key = (sig, n)
        fn = self._small.get(key)
        if fn is None:
            body = self._scorer_body(sig)

            def scorer(params, x):
                self._record_trace(sig, tuple(x.shape))
                return body(params, x)

            fn = jax.jit(scorer, donate_argnums=self._donate)
            self._small[key] = fn
        return fn

    def _super_fn(self, sig: ArchSig) -> Callable:
        """The stacked superbatch dispatch for one arch signature: the
        single-chunk scorer body mapped over stacked per-query params
        and a ``(group, bucket, …)`` input. ``jax.vmap`` lowers the
        conv stack to grouped convolutions (fast on TPU); the "unroll"
        style emits one body per group member instead (CPU). Either
        way: one dispatch covering chunks from several queries, one
        trace per (signature, group size, bucket)."""
        fn = self._super.get(sig)
        if fn is None:
            body = self._scorer_body(sig)
            if self.superbatch == "vmap":
                mapped = jax.vmap(body)
            else:
                def mapped(params, x):
                    outs = [body(jax.tree_util.tree_map(
                        lambda a, g=g: a[g], params), x[g])
                        for g in range(x.shape[0])]
                    return (jnp.stack([p for p, _ in outs]),
                            jnp.stack([c for _, c in outs]))

            def scorer(params, x):
                self._record_trace(sig, tuple(x.shape), grouped=True)
                if self.mesh is None:
                    return mapped(params, x)
                # XLA cannot partition a Pallas kernel itself, so each
                # device runs the body on its own group members (or, for
                # a replicated fallback shape, on all of them)
                spec = P(shd.superbatch_spec(x.shape, self.mesh)[0])
                return jax.shard_map(mapped, mesh=self.mesh, in_specs=spec,
                                     out_specs=spec, check_vma=False)(
                                         params, x)

            fn = jax.jit(scorer, donate_argnums=self._donate)
            self._super[sig] = fn
        return fn

    def trace_count(self, arch=None) -> int:
        if arch is None:
            return sum(self._traces.values())
        return self._traces.get(arch_signature(arch), 0)

    @property
    def n_compiled(self) -> int:
        return len(self._apply) + len(self._small) + len(self._super)

    def shape_vocab(self) -> Dict[str, List[tuple]]:
        """sig-string -> sorted dispatch shapes used so far. Every shape
        traces at most once, so ``traces_per_arch[s] <=
        len(shape_vocab()[s])`` — the bound bench reports record."""
        return {sig_str(sig): sorted(shapes)
                for sig, shapes in self._shape_vocab.items()}

    def dispatch_stats(self) -> Dict[str, int]:
        """Per-path dispatch accounting for bench output."""
        return {
            "calls": self.calls,
            "small_calls": self.small_calls,
            "bucketed_calls": self.bucketed_calls,
            "super_calls": self.super_calls,
            "frames_scored": self.frames_scored,
            "frames_padded": self.frames_padded,
        }

    def mesh_info(self) -> Dict[str, object]:
        """Mesh identification for bench artifacts: every BENCH json
        records where (and across how many devices) it was measured."""
        return {
            "device_count": self.device_count,
            "mesh_shape": (dict(self.mesh.shape)
                           if self.mesh is not None else None),
            "sharded": self.mesh is not None,
        }

    def sharding_fallbacks(self) -> list:
        """Summarized divisibility fallbacks hit so far (dims that
        replicated instead of sharding) — ``explain_fallbacks`` over
        the raw records, for the roofline / bench reports."""
        return shd.explain_fallbacks(self._fallbacks)

    # -- dispatch layers -----------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b <<= 1
        return min(b, self.chunk)

    def is_small(self, sig: ArchSig, n: int) -> bool:
        """Does a batch of ``n`` frames take the lean small-shape path?

        Judged on the batch's *padded* (quantized) size, not ``n``:
        that makes the small and bucketed dispatch-shape vocabularies
        provably disjoint, so no (sig, shape) jit-cache key is ever
        reachable from both layers and each shape traces exactly once.
        (A shape S dispatched bucketed implies some non-small m with
        quantize(m) <= bucket(m) = S, hence S*flops >= small_flops; a
        small dispatch at S requires S*flops < small_flops.) Monotone
        in ``n`` per signature."""
        return self._quantize_small(n) * sig_flops(sig) < self.small_flops

    def _quantize_small(self, n: int) -> int:
        q = self.small_quant
        return max(1, ((n + q - 1) // q) * q) if n else 0

    def _pad_rows(self, x: np.ndarray, to: int) -> np.ndarray:
        m = x.shape[0]
        if m >= to:
            return x
        self.frames_padded += to - m
        return np.concatenate(
            [x, np.zeros((to - m,) + x.shape[1:], np.float32)])

    def _place(self, x, *, grouped: bool):
        """Device placement for one dispatch input. Without a mesh this
        is ``jnp.asarray`` (single device, unchanged fast path); with
        one, stacked superbatches are committed with the group-axis
        ``NamedSharding`` derived from their shape (replicated when the
        group does not divide — recorded fallback) so the jit below
        partitions across devices with bitwise-identical results. Flat
        batches stay on the default device unless ``shard_frames`` opts
        into the bit-unsafe frame-axis sharding. The spec is a pure
        function of the shape, so equal shapes always carry equal
        shardings and the jit cache never sees a (shape, sharding)
        collision."""
        if self.mesh is None:
            return jnp.asarray(x)
        if grouped:
            spec = shd.superbatch_spec(x.shape, self.mesh, self._fallbacks)
        elif self.shard_frames:
            spec = shd.frames_spec(x.shape, self.mesh, self._fallbacks)
        else:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x),
                              jax.sharding.NamedSharding(self.mesh, spec))

    def _dispatch(self, sig: ArchSig, fn: Callable, params, x,
                  *, kind: str):
        """Every jit dispatch funnels through here: counts calls (the
        unit ``calls`` means on every path), records the shape
        vocabulary, and places the input on the mesh (sharded when one
        is configured). Returns on-device arrays."""
        self.calls += 1
        if kind == "small":
            self.small_calls += 1
        elif kind == "super":
            self.super_calls += 1
        else:
            self.bucketed_calls += 1
        self._shape_vocab.setdefault(sig, set()).add(tuple(x.shape))
        with obs.span(obs.SCORE_DISPATCH):
            return fn(params, self._place(x, grouped=(kind == "super")))

    def _dispatch_chunk(self, sig: ArchSig, params, x: np.ndarray):
        """One chunk through the lean or bucketed layer (padding as the
        layer dictates); returns on-device (probs, counts)."""
        m = x.shape[0]
        if self.is_small(sig, m):
            n = self._quantize_small(m)
            return self._dispatch(
                sig, self._small_fn(sig, n), params,
                self._pad_rows(x, n), kind="small")
        b = self._bucket(m)
        return self._dispatch(
            sig, self._bucket_fn(sig), params,
            self._pad_rows(x, b), kind="bucketed")

    # -- scoring -------------------------------------------------------------

    def score_crops(self, params: dict, arch, crops
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Score pre-cropped inputs -> (presence_prob, count) as numpy.
        ``calls`` advances once per jit dispatch (= per chunk)."""
        x = np.asarray(crops, np.float32)
        n = x.shape[0]
        probs = np.empty(n, np.float64)
        counts = np.empty(n, np.float64)
        if n == 0:
            return probs, counts
        sig = arch_signature(arch)
        self.frames_scored += n
        for i in range(0, n, self.chunk):
            xb = x[i:i + self.chunk]
            m = xb.shape[0]
            p, c = self._dispatch_chunk(sig, params, xb)
            with obs.span(obs.SCORE_WAIT):
                probs[i:i + m] = np.asarray(p, np.float64)[:m]
                counts[i:i + m] = np.asarray(c, np.float64)[:m]
        return probs, counts

    def score(self, trained, bank, idxs) -> Tuple[np.ndarray, np.ndarray]:
        """Score frame indices of a ``TrainedOp`` via a FrameBank,
        cropping chunk-by-chunk (keeps peak memory at one chunk)."""
        batcher = ScoreBatcher(self, group_max=1)
        handle = batcher.submit(trained, bank, idxs)
        batcher.flush()
        return handle.result()

    # -- cross-query demand aggregation ---------------------------------------

    def score_demands(self, demands, *, group_max: int = 8
                      ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Score many queries' demands with fewer, larger dispatches.

        ``demands``: list of ``(trained, bank, idxs)`` — one per query
        (different queries have different params and FrameBanks but
        often share an arch *signature*). Batch facade over
        ``ScoreBatcher``: submit everything, flush, resolve. Returns
        ``[(probs, counts)]`` aligned with ``demands``.
        """
        batcher = ScoreBatcher(self, group_max=group_max)
        handles = [batcher.submit(trained, bank, idxs)
                   for trained, bank, idxs in demands]
        batcher.flush()
        return [h.result() for h in handles]


# -- fused dispatch + on-device results ---------------------------------------


class _Out:
    """One dispatch's on-device output; converted to float64 numpy once,
    on first consumption — until then results stay on-device, which is
    what lets JAX async dispatch overlap scoring with host-side work.
    ``on_consume`` (if given) fires at that first conversion — the
    ScoreBatcher uses it to track how many dispatches are in flight,
    which is what makes score/uplink overlap *measurable*."""

    __slots__ = ("p", "c", "_np", "_cb")

    def __init__(self, p, c, on_consume: Optional[Callable] = None):
        self.p, self.c = p, c
        self._np: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._cb = on_consume

    def to_np(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._np is None:
            with obs.span(obs.SCORE_WAIT):
                self._np = (np.asarray(self.p, np.float64),
                            np.asarray(self.c, np.float64))
            self.p = self.c = None          # free the device buffers
            if self._cb is not None:
                self._cb()
                self._cb = None
        return self._np


class ScoreHandle:
    """Future-like per-demand result. ``result()`` blocks on (and
    converts) the device arrays; everything before that is async."""

    def __init__(self, n: int):
        self._probs = np.empty(n, np.float64)
        self._counts = np.empty(n, np.float64)
        self._parts: List[Tuple[int, int, _Out, Optional[int]]] = []
        self._chunks = 0          # chunks submitted, incl. undispatched
        self._done: Optional[Tuple[np.ndarray, np.ndarray]] = None

    @property
    def dispatched(self) -> bool:
        """All chunks issued to the device (results may still be in
        flight — that is the point)."""
        return len(self._parts) == self._chunks

    def _add_part(self, off: int, m: int, out: _Out,
                  row: Optional[int]) -> None:
        self._parts.append((off, m, out, row))

    def result(self) -> Tuple[np.ndarray, np.ndarray]:
        """(probs, counts) float64 numpy, one entry per index."""
        if self._done is None:
            if not self.dispatched:
                raise RuntimeError(
                    "ScoreHandle.result() before all chunks dispatched; "
                    "flush the ScoreBatcher first")
            for off, m, out, row in self._parts:
                p, c = out.to_np()
                if row is not None:
                    p, c = p[row], c[row]
                self._probs[off:off + m] = p[:m]
                self._counts[off:off + m] = c[:m]
            self._parts = []
            self._done = (self._probs, self._counts)
        return self._done


class ScoreBatcher:
    """Accumulates score demands and issues fused dispatches eagerly.

    ``submit`` cuts a demand into chunks immediately (host-side crop +
    pad), sends small chunks straight through the lean layer, and
    queues bucketed chunks per (signature, bucket). Three watermarks
    turn queues into dispatches:

      * **group_max** — a queue reaching ``group_max`` dispatches
        immediately as one stacked superbatch (the high-watermark);
      * **bucket complete** — ``fire_complete(possible_sigs)`` lets a
        scheduler that knows which signatures can still receive chunks
        (the FleetScheduler tracks every unblocked query's last-known
        arch) dispatch the queues that *cannot grow any further* —
        without this, mixed-arch fleets whose per-signature fan-in
        never reaches ``group_max`` issue nothing until the barrier
        and forfeit all score/uplink overlap;
      * **flush** — the no-ticks barrier dispatches every remainder
        (singles go through the bucketed layer so no superbatch shape
        is traced for a leftover group size of 1).

    Dispatches return immediately with on-device results
    (:class:`ScoreHandle`); callers resolve them as late as possible,
    letting device compute overlap host work in between. ``in_flight``
    counts dispatches whose results have not been consumed yet — the
    observable the fleet's overlap measurement integrates over. Every
    layout this class may choose is bit-identical to single-demand
    scoring, so watermark choices are pure performance tuning.
    """

    def __init__(self, runtime: OperatorRuntime, *, group_max: int = 8):
        self.rt = runtime
        self.group_max = max(int(group_max), 1)
        self._queues: Dict[Tuple[ArchSig, int], List[tuple]] = {}
        self.eager_dispatches = 0    # issued before flush(), any watermark
        self.watermark_fires = {"group_max": 0, "bucket_complete": 0}
        self.in_flight = 0           # dispatched, results not yet consumed

    def pending(self) -> int:
        """Chunks queued but not yet dispatched."""
        return sum(len(q) for q in self._queues.values())

    def _out(self, p, c) -> _Out:
        """Wrap one dispatch's device arrays with in-flight tracking."""
        self.in_flight += 1
        return _Out(p, c, on_consume=self._consumed)

    def _consumed(self) -> None:
        self.in_flight -= 1

    def submit(self, trained, bank, idxs) -> ScoreHandle:
        """Enqueue one demand; returns its handle (resolve after the
        batcher is flushed)."""
        with obs.span(obs.SCORE_SUBMIT):
            rt = self.rt
            arch = trained.arch
            sig = arch_signature(arch)
            idxs = np.asarray(idxs, np.int64)
            handle = ScoreHandle(len(idxs))
            if len(idxs) == 0:
                return handle
            rt.frames_scored += len(idxs)
            for i in range(0, len(idxs), rt.chunk):
                sel = idxs[i:i + rt.chunk]
                x = np.asarray(bank.crops(sel, arch.region, arch.input_size),
                               np.float32)
                m = x.shape[0]
                handle._chunks += 1
                if self.group_max == 1 or rt.is_small(sig, m):
                    p, c = rt._dispatch_chunk(sig, trained.params, x)
                    handle._add_part(i, m, self._out(p, c), None)
                    continue
                b = rt._bucket(m)
                q = self._queues.setdefault((sig, b), [])
                q.append((handle, i, m, trained.params, rt._pad_rows(x, b)))
                if len(q) >= self.group_max:
                    self._dispatch_group(sig, q)
                    self._queues[(sig, b)] = []
                    self.eager_dispatches += 1
                    self.watermark_fires["group_max"] += 1
            return handle

    def fire_complete(self, possible_sigs: Optional[Set[ArchSig]]) -> None:
        """The bucket-complete watermark: dispatch every queue whose
        signature is *not* in ``possible_sigs`` — the caller asserts no
        future chunk can join those queues before the next flush, so
        waiting buys nothing and issuing now buys overlap. ``None``
        means the caller cannot rule anything out (some query's next
        signature is unknown): no-op, the conservative default."""
        if possible_sigs is None:
            return
        for (sig, _b), q in list(self._queues.items()):
            if q and sig not in possible_sigs:
                self._dispatch_group(sig, q)
                self._queues[(sig, _b)] = []
                self.eager_dispatches += 1
                self.watermark_fires["bucket_complete"] += 1

    def flush(self) -> None:
        """Dispatch every queued partial group (the no-ticks-pending
        watermark); afterwards all submitted handles are resolvable."""
        for (sig, _b), q in self._queues.items():
            if q:
                self._dispatch_group(sig, q)
        self._queues.clear()

    def _dispatch_group(self, sig: ArchSig, group: List[tuple]) -> None:
        rt = self.rt
        if len(group) == 1:
            handle, off, m, params, x = group[0]
            p, c = rt._dispatch(sig, rt._bucket_fn(sig), params, x,
                                kind="bucketed")
            handle._add_part(off, m, self._out(p, c), None)
            return
        with obs.span(obs.SCORE_STACK):
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *[g[3] for g in group])
            xs = np.stack([g[4] for g in group])
        ps, cs = rt._dispatch(sig, rt._super_fn(sig), stacked, xs,
                              kind="super")
        out = self._out(ps, cs)
        for row, (handle, off, m, _params, _x) in enumerate(group):
            handle._add_part(off, m, out, row)


# -- trace accounting ---------------------------------------------------------


def sig_str(sig: ArchSig) -> str:
    """Stable human-readable key for an arch signature (bench reports)."""
    return f"L{sig[0]}c{sig[1]}d{sig[2]}s{sig[3]}"


class RetraceError(AssertionError):
    """A (arch signature, batch shape) was traced more than once."""


class TraceGuard:
    """Asserts the one-trace-per-(arch signature, batch shape) invariant
    over a code region.

    The runtime's whole performance story is the compilation cache:
    each arch signature compiles once per dispatch shape (quantized
    small shape, power-of-two bucket, or (group, bucket) superbatch)
    and every later call is a cache hit. A *retrace* — the same
    (signature, shape) traced twice — means something destroyed cache
    keys (params dtype drift, a rebuilt jit wrapper, an unbucketed
    shape) and silently re-pays compile time per call; exactly the
    tracing/dispatch overhead flagged in the ROADMAP. Usage::

        with TraceGuard(runtime) as guard:
            ... score ...
        # raises RetraceError on exit if any (sig, shape) retraced
        guard.traces_per_arch   # {"L2c8d16s25": 3, ...} for reports

    ``check_on_exit=False`` turns the exit check off for callers that
    only want the accounting (benchmarks recording traces_per_arch).
    Static-analysis counterpart: rules TRC001-004 in ``repro.analysis``.
    """

    def __init__(self, runtime: Optional[OperatorRuntime] = None,
                 *, check_on_exit: bool = True):
        self.runtime = runtime
        self.check_on_exit = check_on_exit
        self._before: Dict[Tuple[ArchSig, tuple], int] = {}

    def __enter__(self) -> "TraceGuard":
        if self.runtime is None:
            self.runtime = get_runtime()
        self._before = dict(self.runtime._shape_traces)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None and self.check_on_exit:
            self.check()
        return False

    @property
    def new_traces(self) -> Dict[Tuple[ArchSig, tuple], int]:
        """(sig, shape-key) -> traces recorded inside the region."""
        out: Dict[Tuple[ArchSig, tuple], int] = {}
        for key, n in self.runtime._shape_traces.items():
            delta = n - self._before.get(key, 0)
            if delta:
                out[key] = delta
        return out

    @property
    def traces_per_arch(self) -> Dict[str, int]:
        """sig-string -> traces inside the region, summed over shapes."""
        out: Dict[str, int] = {}
        for (sig, _shape), delta in self.new_traces.items():
            key = sig_str(sig)
            out[key] = out.get(key, 0) + delta
        return out

    def check(self) -> None:
        """Raise RetraceError if any (sig, shape) traced inside the
        region had already been traced (or traced twice inside it)."""
        bad = []
        for key, delta in self.new_traces.items():
            total = self._before.get(key, 0) + delta
            if total > 1:
                sig, shape = key
                bad.append(f"  {sig_str(sig)} shape={shape}: "
                           f"{total} traces ({delta} in guarded region)")
        if bad:
            raise RetraceError(
                "retrace detected — each (arch signature, batch shape) "
                "must trace exactly once per runtime:\n" + "\n".join(bad))


# -- process-global runtime ---------------------------------------------------

_RUNTIME: Optional[OperatorRuntime] = None


def get_runtime() -> OperatorRuntime:
    """The shared per-process runtime (one compilation cache per host)."""
    global _RUNTIME
    if _RUNTIME is None:
        _RUNTIME = OperatorRuntime()
    return _RUNTIME


def set_runtime(rt: Optional[OperatorRuntime]) -> Optional[OperatorRuntime]:
    """Swap the process-global runtime (tests/benchmarks); returns the
    previous one so callers can restore it."""
    global _RUNTIME
    prev, _RUNTIME = _RUNTIME, rt
    return prev
