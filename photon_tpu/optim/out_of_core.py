"""Out-of-core fixed-effect training: host-resident row chunks streamed
through the accelerator per pass.

Why: a single TPU's HBM cannot hold config-5-scale data (100M rows x 32 nnz
= 25.6 GB of ELL vs 16 GB HBM), and the in-core path materializes the whole
dataset as device arrays (``io/data_reader.py:102``). The reference never
held the dataset on one box either — its distributed objective aggregates
partition-wise value+grad contributions (⟦ValueAndGradientAggregator⟧ via
Spark ``treeAggregate``, SURVEY.md §2.2 "Distributed objective"). This
module is that design re-cast for one accelerator whose bottleneck is HBM
capacity, not cluster size:

* Only the ELL arrays (``idx``/``val`` — the O(dataset) payload) stay in
  host RAM, split into fixed-shape row chunks; every optimizer pass streams
  them through jitted per-chunk kernels (one compile per chunk shape).
* Everything O(rows) or O(dim) is device-resident: labels/offsets/weights,
  the maintained margins z = Xw (+offsets), the direction margins, w, the
  gradient, and the L-BFGS history — so line-search probes are elementwise
  device math over the resident margins, never a data pass (the
  incremental-score trick of ``optim/lbfgs.py:310`` — same 2 streamed
  passes per iteration: direction matvec + gradient rmatvec).
* The L-BFGS math itself REUSES the in-core pieces (``two_loop_direction``,
  ``update_history``, ``check_convergence`` semantics, Armijo constants),
  so out-of-core and in-core solves agree to numerical noise — tested.

Scope: smooth L2 GLM objectives (all four pointwise losses) via
:class:`OutOfCoreLBFGS`, and L1/elastic-net via :class:`OutOfCoreOWLQN`
(the orthant machinery — pseudo-gradient, alignment, projection — is
elementwise in coefficient space, so it streams exactly like the smooth
solver; only the line search costs one extra pass per probe because the
orthant projection invalidates the resident direction margins). TRON,
priors, SIMPLE/FULL variance and normalization remain in-core features;
the driver auto-routes fixed-effect solves here when the dataset would
blow the device-data budget.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.batch import SparseFeatures
from photon_tpu.faults import fault_point
from photon_tpu.obs import trace_span
from photon_tpu.optim.base import (
    FUNCTION_VALUES_CONVERGED,
    MAX_ITERATIONS,
    NOT_CONVERGED,
    OptimizerConfig,
    OptimizerResult,
    check_convergence,
)
from photon_tpu.optim.lbfgs import (
    LBFGSHistory,
    empty_history,
)
from photon_tpu.optim.lbfgs import two_loop_direction as _two_loop_eager
from photon_tpu.optim.lbfgs import update_history as _update_history_eager
from photon_tpu.optim.owlqn import orthant, pseudo_gradient

# The out-of-core loops run in HOST Python (streams + checkpoints force
# that), so unlike the in-core solvers these helpers would execute as a
# cascade of EAGER ops — every eager op is its own dispatch to the
# device. Jit them once (pinning the default dot, a plain
# jnp.dot, out of the traced signature): one compiled program per call
# site instead of dozens of dispatches per iteration.
two_loop_direction = jax.jit(lambda g, hist: _two_loop_eager(g, hist))
update_history = jax.jit(lambda hist, s, y: _update_history_eager(hist, s, y))


@jax.jit
def _reg_at_t(w, d, t, l2v):
    """½·Σ l2v·(w + t·d)² — the line-search probe's regularizer term, one
    compiled program instead of 3-4 eager O(dim) dispatches per probe
    (every arg traced, so neither backtracking nor a λ-sweep recompiles)."""
    wt = w + t * d
    return 0.5 * jnp.sum(l2v * wt * wt)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class _HostChunk:
    """One fixed-shape row chunk; the streamed (host-RAM) part is idx/val."""

    idx: np.ndarray   # [C, K] int32, ghost-padded (col == dim, val == 0)
    val: np.ndarray   # [C, K] float (f32, or bf16 via value_dtype)


@dataclasses.dataclass
class ChunkedGLMData:
    """Fixed-effect dataset as host-resident ELL chunks + device row data.

    ``labels``/``offsets``/``weights`` are per-chunk DEVICE arrays (weights
    carry 0 on padding rows, so padded rows contribute nothing — same ghost
    convention as ``LabeledBatch``). ``n_rows`` is the true (unpadded) row
    count.

    Sharding contract: a MESH solve rebinds ``labels``/``offsets``/
    ``weights`` IN PLACE to mesh-sharded device arrays (deliberate: at
    config-5 scale the unsharded originals are ~1.2 GB of HBM that must not
    sit next to their own sharded copies, and a λ-sweep re-enters with
    already-sharded arrays as no-op puts). The object is therefore bound to
    that mesh afterwards: reusing it under a DIFFERENT mesh re-shards it to
    the new mesh (one extra put per array), while host-side consumers
    (``labels_np``/``scores_out_of_core``) read sharded arrays fine on a
    single process. Don't interleave two meshes' solves over one instance
    in a tight loop — put churn, not correctness, is the cost.
    """

    chunks: list
    labels: list
    offsets: list
    weights: list
    dim: int
    n_rows: int
    chunk_rows: int

    @classmethod
    def from_arrays(
        cls,
        idx: np.ndarray,
        val: np.ndarray,
        labels: np.ndarray,
        dim: int,
        offsets: Optional[np.ndarray] = None,
        weights: Optional[np.ndarray] = None,
        chunk_rows: int = 1 << 20,
        value_dtype=None,
    ) -> "ChunkedGLMData":
        n, k = idx.shape
        if offsets is None:
            offsets = np.zeros(n, np.float32)
        if weights is None:
            weights = np.ones(n, np.float32)
        n_chunks = max(1, math.ceil(n / chunk_rows))
        chunks, lab, off, wgt = [], [], [], []
        for c in range(n_chunks):
            lo, hi = c * chunk_rows, min((c + 1) * chunk_rows, n)
            m = hi - lo
            pad = chunk_rows - m
            ci = np.full((chunk_rows, k), dim, np.int32)
            cv = np.zeros((chunk_rows, k), np.float32)
            ci[:m] = idx[lo:hi]
            cv[:m] = val[lo:hi]
            if value_dtype is not None:
                cv = np.asarray(jnp.asarray(cv).astype(value_dtype))
            chunks.append(_HostChunk(idx=ci, val=cv))
            lab.append(jnp.asarray(np.pad(labels[lo:hi], (0, pad))))
            off.append(jnp.asarray(np.pad(offsets[lo:hi], (0, pad))))
            wgt.append(jnp.asarray(np.pad(weights[lo:hi], (0, pad))))
        return cls(chunks=chunks, labels=lab, offsets=off, weights=wgt,
                   dim=dim, n_rows=n, chunk_rows=chunk_rows)

    @classmethod
    def from_stream(
        cls,
        chunk_iter,
        shard: str,
        dim: int,
        chunk_rows: int = 1 << 20,
        value_dtype=None,
        on_chunk=None,
    ) -> "ChunkedGLMData":
        """Build from ``StreamingAvroReader.iter_chunks`` output WITHOUT
        ever materializing the dataset as one device array — the whole point
        of this path (streamed chunks hold host numpy ELL; see
        ``io/streaming.py`` chunk construction). Streamed chunk widths (K)
        may vary; the OOC chunks use the global max so one kernel compile
        serves every chunk.

        ``on_chunk(i, host_chunk, labels, offsets, weights)``, when given, is
        invoked the moment chunk ``i`` is assembled — streaming callers use
        it to FAIL FAST on invalid data (a NaN in the first chunk of a 100M
        row stream must raise within seconds, not after the whole dataset is
        decoded into host RAM). An exception from the callback aborts the
        stream. Note the ELL width may still grow after a chunk is handed
        out (``regrow`` ghost-pads flushed chunks in place); ghost padding
        never changes a chunk's validity."""
        # Streamed chunks are consumed ONE AT A TIME (peak extra memory:
        # one assembly buffer) — materializing the iterator first would
        # double host RAM at exactly the scale this path exists for. The
        # ELL width K may grow mid-stream; already-flushed chunks are then
        # ghost-padded out to the new width (one chunk's copy at a time).
        cur_k = 1
        idx = np.full((chunk_rows, cur_k), dim, np.int32)
        val = np.zeros((chunk_rows, cur_k), np.float32)
        lab = np.zeros(chunk_rows, np.float32)
        off = np.zeros(chunk_rows, np.float32)
        wgt = np.zeros(chunk_rows, np.float32)
        out = cls(chunks=[], labels=[], offsets=[], weights=[], dim=dim,
                  n_rows=0, chunk_rows=chunk_rows)
        fill = 0

        def regrow(new_k: int):
            nonlocal cur_k, idx, val
            for i, h in enumerate(out.chunks):
                gi = np.full((chunk_rows, new_k), dim, np.int32)
                gv = np.zeros((chunk_rows, new_k), h.val.dtype)
                gi[:, :cur_k] = h.idx
                gv[:, :cur_k] = h.val
                out.chunks[i] = _HostChunk(idx=gi, val=gv)
            gi = np.full((chunk_rows, new_k), dim, np.int32)
            gv = np.zeros((chunk_rows, new_k), np.float32)
            gi[:, :cur_k] = idx
            gv[:, :cur_k] = val
            idx, val, cur_k = gi, gv, new_k

        def flush():
            nonlocal fill
            cv = val
            if value_dtype is not None:
                cv = np.asarray(jnp.asarray(val).astype(value_dtype))
            out.chunks.append(_HostChunk(idx=idx.copy(), val=cv.copy()))
            # COPY before jnp.asarray: on CPU backends jax may zero-copy an
            # aligned numpy buffer, and these fill buffers are zeroed and
            # reused for the next chunk — aliasing would corrupt every
            # already-appended chunk.
            out.labels.append(jnp.asarray(lab.copy()))
            out.offsets.append(jnp.asarray(off.copy()))
            out.weights.append(jnp.asarray(wgt.copy()))
            if on_chunk is not None:
                on_chunk(len(out.chunks) - 1, out.chunks[-1],
                         out.labels[-1], out.offsets[-1], out.weights[-1])
            idx[:] = dim
            val[:] = 0.0
            lab[:] = 0.0
            off[:] = 0.0
            wgt[:] = 0.0
            fill = 0

        for c in chunk_iter:
            sf = c.features[shard]
            ci, cv = np.asarray(sf.idx), np.asarray(sf.val)
            if ci.shape[1] > cur_k:
                regrow(ci.shape[1])
            out.n_rows += c.n_rows
            at = 0
            while at < c.n_rows:
                take = min(chunk_rows - fill, c.n_rows - at)
                sl = slice(fill, fill + take)
                idx[sl, : ci.shape[1]] = ci[at:at + take]
                val[sl, : cv.shape[1]] = cv[at:at + take]
                lab[sl] = c.labels[at:at + take]
                off[sl] = c.offsets[at:at + take]
                wgt[sl] = c.weights[at:at + take]
                fill += take
                at += take
                if fill == chunk_rows:
                    flush()
        if fill:
            flush()
        if not out.chunks:
            raise ValueError("no rows streamed")
        return out

    def rechunk(self, factor: int = 2) -> "ChunkedGLMData":
        """The same dataset re-cut at ``chunk_rows / factor`` — the OOM
        degradation ladder's out-of-core rung (docs/robustness.md
        §"Memory pressure"): when a streamed pass OOMs, halving the chunk
        shape halves the live per-chunk device footprint, and the solve
        re-enters over smaller chunks with identical (weight-0 ghost-
        padded) row content. Raises ValueError when no smaller cut exists
        (``chunk_rows == 1``)."""
        if factor < 2:
            raise ValueError(f"rechunk factor must be >= 2, got {factor}")
        new_rows = -(-self.chunk_rows // factor)  # ceil division
        if new_rows >= self.chunk_rows:
            raise ValueError(
                f"cannot rechunk below chunk_rows={self.chunk_rows}")
        k = self.chunks[0].idx.shape[1]
        chunks, lab, off, wgt = [], [], [], []
        for i, c in enumerate(self.chunks):
            for lo in range(0, self.chunk_rows, new_rows):
                hi = min(lo + new_rows, self.chunk_rows)
                pad = new_rows - (hi - lo)
                ci = c.idx[lo:hi]
                cv = c.val[lo:hi]
                if pad:
                    ci = np.concatenate(
                        [ci, np.full((pad, k), self.dim, np.int32)])
                    cv = np.concatenate(
                        [cv, np.zeros((pad, k), c.val.dtype)])
                chunks.append(_HostChunk(idx=ci, val=cv))
                for src, dst in ((self.labels, lab), (self.offsets, off),
                                 (self.weights, wgt)):
                    piece = src[i][lo:hi]
                    if pad:
                        piece = jnp.pad(piece, (0, pad))
                    dst.append(piece)
        return ChunkedGLMData(
            chunks=chunks, labels=lab, offsets=off, weights=wgt,
            dim=self.dim, n_rows=self.n_rows, chunk_rows=new_rows)

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    def streamed_bytes_per_pass(self) -> int:
        c = self.chunks[0]
        return self.n_chunks * (c.idx.nbytes + c.val.nbytes)

    def labels_np(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(x) for x in self.labels])[: self.n_rows]

    def weights_np(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(x) for x in self.weights])[: self.n_rows]


class StreamPrimer:
    """First optimizer pass computed per chunk AS IT STREAMS IN.

    Pass an instance as ``ChunkedGLMData.from_stream(..., on_chunk=primer)``:
    the moment chunk *i* is assembled, its ELL arrays go to device (through
    the sweep cache when given, so pass 1 of the solve reuses the upload)
    and the chunk's initial scores ``z = X·w0 + offsets`` and data
    value/gradient contribution are computed inside an
    ``optim.stream_init_pass`` span — so with a prefetched chunk iterator
    (``io/prefetch.py``) the solve's init pass overlaps block decode instead
    of running after it, and ``optimize(..., primed=primer.primed())`` skips
    its two init passes entirely. The per-chunk kernels and accumulation
    order are EXACTLY the solver's own (``_kernels_for``; f/g accumulate
    chunk 0..n−1), so a primed solve is bit-identical to an unprimed one.

    Single-device only: a mesh solve row-shards its resident vectors and
    ignores ``primed`` (documented in ``optimize``).
    """

    def __init__(self, loss, dim: int, w0=None, device_cache=None):
        self.dim = int(dim)
        self._kernels = _kernels_for(loss, dim)
        self.w0 = (jnp.zeros((dim,), jnp.float32) if w0 is None
                   else jnp.asarray(w0, jnp.float32))
        self.device_cache = device_cache
        self.z: list = []
        self.fd = jnp.zeros((), jnp.float32)
        self.gd = jnp.zeros((dim,), jnp.float32)
        self._fed_keys: list = []
        self._chunks_seen: list = []
        self._ell_width: Optional[int] = None

    def __call__(self, i, host_chunk, labels, offsets, weights) -> None:
        k_matvec, _k_probe, _k_probe_t, k_grad = self._kernels
        # from_stream REGROWS already-flushed chunks in place when the ELL
        # width widens mid-stream: every pin this primer made for the old
        # (now freed) arrays can never be hit again — discard them so the
        # budget holds live data, not orphans. The z/f/g already computed
        # stay exact (regrow only adds ghost padding).
        width = int(host_chunk.idx.shape[1])
        if (self.device_cache is not None and self._ell_width is not None
                and width != self._ell_width):
            for k in self._fed_keys:
                self.device_cache.discard(k)
            self._fed_keys.clear()
        self._ell_width = width
        # Feed FIRST, outside the compute span: the timeline analyzer's
        # overlap report must never count a same-thread H2D nested inside a
        # compute span as "ingest concurrent with compute".
        ci, cv = _feed_chunk(host_chunk, self.device_cache,
                             lambda a: jnp.asarray(a))
        if self.device_cache is not None:
            self._fed_keys.append(("ooc_ell", id(host_chunk.idx)))
        self._chunks_seen.append(host_chunk)
        with trace_span("optim.stream_init_pass", cat="optim", chunk=i,
                        rows=int(labels.shape[0])):
            z = k_matvec(self.w0, ci, cv, offsets)
            fc, gc = k_grad(z, labels, weights, ci, cv)
            self.z.append(z)
            self.fd = self.fd + fc
            self.gd = self.gd + gc

    def primed(self) -> dict:
        """State for ``optimize(..., primed=...)``: resident margins plus
        the DATA-ONLY value/gradient at ``w0`` (the solver adds its own
        regularizer terms), stamped with the chunk objects the pass ran
        over so a prime from a DIFFERENT dataset can never be trusted."""
        return {"z": self.z, "fd": self.fd, "gd": self.gd, "w0": self.w0,
                "chunks": list(self._chunks_seen)}


def _feed_chunk(c: "_HostChunk", cache, put):
    """(idx, val) of one host chunk on device — through the sweep cache when
    given (multi-sweep/multi-pass solves stop re-uploading), else a traced
    one-shot transfer. Keys by the ARRAY identity so a regrown chunk (new
    arrays) re-uploads instead of serving stale width."""
    if cache is not None and cache.enabled:
        return cache.get_or_put(
            ("ooc_ell", id(c.idx)),
            c.idx.nbytes + c.val.nbytes,
            lambda: (put(c.idx), put(c.val)),
            # Pin the keyed host array: a regrown chunk frees its original
            # arrays, and a recycled id() must never alias a NEW chunk onto
            # this (stale) device entry.
            retain=c.idx,
        )
    from photon_tpu.obs import trace_span as _span

    with _span("ingest.device_put", cat="ingest",
               bytes=int(c.idx.nbytes + c.val.nbytes), cached=False):
        return put(c.idx), put(c.val)


@functools.lru_cache(maxsize=None)
def _matvec_for(dim: int):
    @jax.jit
    def k_matvec(w, idx, val, offsets):
        sf = SparseFeatures(idx=idx, val=val, dim=dim)
        return sf.matvec(w) + offsets

    return k_matvec


@functools.lru_cache(maxsize=None)
def _kernels_for(loss, dim: int):
    """(matvec, probe, probe_at_t, grad) jitted per-chunk kernels. Cached
    on the (loss, dim) pair — `loss_for_task` returns per-task singletons,
    so a regularization sweep never recompiles (λ enters host-side only)."""

    @jax.jit
    def k_probe(z, labels, weights):
        return jnp.sum(weights * loss.loss(z, labels))

    @jax.jit
    def k_probe_at_t(z, zd, t, labels, weights):
        # Fused line-search probe over RESIDENT margins: one compiled
        # program instead of an eager z+t·zd add (a full chunk-sized
        # temporary + an extra dispatch per chunk per probe). ``t`` is a
        # traced scalar so backtracking never recompiles.
        return jnp.sum(weights * loss.loss(z + t * zd, labels))

    @jax.jit
    def k_grad(z, labels, weights, idx, val):
        lv, d1 = loss.loss_and_d1(z, labels)
        sf = SparseFeatures(idx=idx, val=val, dim=dim)
        return jnp.sum(weights * lv), sf.rmatvec(weights * d1)

    return _matvec_for(dim), k_probe, k_probe_at_t, k_grad


@functools.lru_cache(maxsize=None)
def _kernels_for_spmd(loss, dim: int, mesh, axes: tuple):
    """Explicit-collective variants of :func:`_kernels_for`: every kernel is
    a ``shard_map`` body over the row axis with ONE ``lax.psum`` where the
    dense path has a row reduction — the out-of-core consumption of the
    ``parallel/spmd_objective`` pattern (treeAggregate ≙ psum, SURVEY.md
    §2.2). Same signatures, same results to fp noise; selected by
    ``OutOfCoreLBFGS(collectives="shard_map")``. Cached per
    (loss, dim, mesh, axes) so a λ-sweep never recompiles."""
    from functools import partial as _partial

    from jax import lax
    from jax.sharding import PartitionSpec as P

    from photon_tpu.parallel.mesh import shard_map

    row, ell = P(axes), P(axes, None)
    smap = _partial(shard_map, mesh=mesh)

    @jax.jit
    @_partial(smap, in_specs=(P(), ell, ell, row), out_specs=row)
    def k_matvec(w, idx, val, offsets):
        sf = SparseFeatures(idx=idx, val=val, dim=dim)
        return sf.matvec(w) + offsets

    @jax.jit
    @_partial(smap, in_specs=(row, row, row), out_specs=P())
    def k_probe(z, labels, weights):
        return lax.psum(jnp.sum(weights * loss.loss(z, labels)), axes)

    @jax.jit
    @_partial(smap, in_specs=(row, row, P(), row, row), out_specs=P())
    def k_probe_at_t(z, zd, t, labels, weights):
        return lax.psum(
            jnp.sum(weights * loss.loss(z + t * zd, labels)), axes)

    @jax.jit
    @_partial(smap, in_specs=(row, row, row, ell, ell),
              out_specs=(P(), P()))
    def k_grad(z, labels, weights, idx, val):
        lv, d1 = loss.loss_and_d1(z, labels)
        sf = SparseFeatures(idx=idx, val=val, dim=dim)
        return (lax.psum(jnp.sum(weights * lv), axes),
                lax.psum(sf.rmatvec(weights * d1), axes))

    return k_matvec, k_probe, k_probe_at_t, k_grad


def _mesh_puts(mesh, data_axis, chunk_rows: int):
    """``(put_row, put_ell, put_rep)`` placement helpers shared by every
    streamed solver: row-sharded resident vectors, row-sharded ELL chunk
    streams, replicated coefficient-space state (SURVEY.md §2.6 P1 × OOC).
    ``data_axis`` may be one mesh axis or a tuple (``("dcn", "data")`` on a
    2-level multi-slice mesh). With no mesh all three are the identity.

    The row/ELL puts are the "fan out per shard" half of the streamed data
    path: ``jax.device_put`` with a NamedSharding splits the host chunk
    into per-device shards and issues each shard's H2D directly to its
    device — wrapped in ``pipelined_puts`` by ``ell_feed`` so shard
    transfers for chunk N+1 overlap chunk N's compute."""
    if mesh is None:
        def ident(a):
            return a

        return ident, ident, ident
    from jax.sharding import NamedSharding, PartitionSpec

    from photon_tpu.parallel.mesh import axes_size, axis_tuple

    axes = axis_tuple(data_axis)
    nsh = axes_size(mesh, axes)
    if chunk_rows % nsh != 0:
        raise ValueError(
            f"chunk_rows={chunk_rows} must divide evenly over "
            f"mesh axis {data_axis!r} ({nsh} devices) for "
            "row-sharded streaming"
        )
    _row = NamedSharding(mesh, PartitionSpec(axes))
    _ell = NamedSharding(mesh, PartitionSpec(axes, None))
    _rep = NamedSharding(mesh, PartitionSpec())

    def put_row(a):
        return jax.device_put(a, _row)

    def put_ell(a):
        return jax.device_put(a, _ell)

    def put_rep(a):
        return jax.device_put(a, _rep)

    return put_row, put_ell, put_rep


@dataclasses.dataclass(frozen=True)
class OutOfCoreLBFGS:
    """Host-loop L-BFGS over a :class:`ChunkedGLMData` (see module doc)."""

    loss: object                      # PointwiseLoss
    l2_weight: float = 0.0
    reg_mask: Optional[Array] = None
    config: OptimizerConfig = OptimizerConfig()
    # Called after every iteration with (it, value, grad_norm, passes).
    # Streamed passes can take minutes each at scale; liveness signals
    # (driver logs, autopilot stall detection) hang off this.
    progress: Optional[object] = None
    # Per-iteration checkpoint/resume (.npz written atomically after an
    # accepted step). A config-5-scale solve runs for hours, so a killed
    # solve must restart at iteration k, not 0. Scores (n_rows floats) are NOT stored
    # — they rebuild from w in one streamed pass on resume. Saves throttle
    # to one per ``checkpoint_min_interval_s`` (after the first): at 10M+
    # features a save is ~0.9 GB of npz, and losing <interval of work is
    # the same accepted trade as the scores-rebuild pass.
    checkpoint_path: Optional[str] = None
    checkpoint_min_interval_s: float = 60.0
    # Data-parallel streaming (SURVEY.md §2.6 P1 × out-of-core): with a
    # Mesh, every streamed chunk is device_put ROW-SHARDED over
    # ``data_axis`` while w/direction stay replicated — GSPMD partitions
    # the per-chunk kernels and inserts the cross-device reductions
    # (value/grad all-reduce), so a pod streams each pass at aggregate
    # H2D + HBM bandwidth. This is how the config-5 shape maps to a
    # v5e-256: host-resident chunks per process, rows sharded over the
    # mesh, one collective per pass — the reference's treeAggregate
    # re-cast as GSPMD (SURVEY.md §2.2 "Distributed objective").
    mesh: Optional[object] = None
    data_axis: str = "data"
    # Collective lowering under a mesh: "gspmd" (default — sharded inputs,
    # XLA inserts the all-reduces) or "shard_map" (explicit psum kernels
    # from _kernels_for_spmd — hand-placed collectives for multi-slice
    # meshes / auditability; same results to fp noise, tested).
    collectives: str = "gspmd"
    # Device-resident sweep cache (photon_tpu/data/device_cache.py): streamed
    # ELL chunks pin on device after the first pass that touches them, so a
    # multi-iteration solve (and a multi-sweep GAME fit re-entering it) stops
    # re-uploading the dataset — budget-gated, spills back to streaming.
    device_cache: Optional[object] = None

    # -- jitted per-chunk kernels -----------------------------------------

    def _kernels(self, dim: int):
        # Module-level cache: kernels depend only on (loss, dim), NOT on
        # the reg weight, so a driver λ-sweep shares one compile across the
        # whole grid (the in-core sweep makes the same guarantee).
        return _kernels_for(self.loss, dim)

    # -- scaffolding shared with OutOfCoreOWLQN ---------------------------

    def _streams(self, data: ChunkedGLMData):
        """Shard the resident row vectors (REBINDING onto ``data`` — see
        the class doc's sharding contract) and return the streamed-pass
        closures ``(put_rep, stream_scores, data_value, data_value_at_t,
        stream_grad)``
        every out-of-core solver loop is built from."""
        if self.mesh is not None and self.collectives == "shard_map":
            from photon_tpu.parallel.mesh import axis_tuple

            k_matvec, k_probe, k_probe_at_t, k_grad = _kernels_for_spmd(
                self.loss, data.dim, self.mesh,
                tuple(axis_tuple(self.data_axis)))
        elif self.collectives not in ("gspmd", "shard_map"):
            raise ValueError(
                f"collectives must be 'gspmd' or 'shard_map', "
                f"got {self.collectives!r}")
        else:
            k_matvec, k_probe, k_probe_at_t, k_grad = self._kernels(data.dim)
        put_row, put_ell, put_rep = _mesh_puts(
            self.mesh, self.data_axis, data.chunk_rows
        )
        labels = data.labels = [put_row(x) for x in data.labels]
        offsets = data.offsets = [put_row(x) for x in data.offsets]
        weights = data.weights = [put_row(x) for x in data.weights]

        # The no-mesh put is an EXPLICIT device commit (jnp.asarray), not
        # the identity: relying on the kernel call's implicit conversion
        # would re-upload every pass even when the sweep cache "holds" the
        # chunk (it would be pinning host numpy). Mesh solves keep the
        # sharded device_put, which commits directly to the right layout.
        put_dev = put_ell if self.mesh is not None else jnp.asarray

        def feed_one(c):
            # Chaos hook: error="device_oom" per streamed chunk drives the
            # halve-chunk_rows degradation ladder in optimize() on CPU.
            fault_point("optim.ooc_chunk", chunk_rows=data.chunk_rows)
            return _feed_chunk(c, self.device_cache, put_dev)

        def ell_feed():
            """Per-pass (idx, val) device feed, DOUBLE-BUFFERED: chunk i+1's
            transfer is issued before chunk i is handed to its kernel, so an
            async backend overlaps the next H2D with the current compute.
            Chunks pinned by the sweep cache skip the transfer entirely."""
            from photon_tpu.io.prefetch import pipelined_puts

            return pipelined_puts(data.chunks, feed_one, ahead=1)

        # Per-chunk compute spans (cat "optim") cover ONLY the kernel call;
        # the feed is pulled from the generator BEFORE the span opens, so
        # the analyzer's ingest/compute overlap never credits a same-thread
        # serial H2D as concurrency. (Spans measure dispatch wall, the
        # repo-wide convention for async backends.)
        def stream_scores(wv, with_offsets=True):
            zero = jnp.zeros_like(offsets[0])
            out = []
            for i, (ci, cv) in enumerate(ell_feed()):
                with trace_span("optim.ooc_scores_chunk", cat="optim",
                                chunk=i):
                    out.append(
                        k_matvec(wv, ci, cv,
                                 offsets[i] if with_offsets else zero)
                    )
            return out

        def data_value(z_chunks):
            with trace_span("optim.ooc_probe", cat="optim",
                            chunks=len(z_chunks)):
                return sum(
                    k_probe(z, labels[i], weights[i])
                    for i, z in enumerate(z_chunks)
                )

        def data_value_at_t(z_chunks, zd_chunks, t):
            """Line-search probe f_data(z + t·zd), fused per chunk."""
            t = jnp.asarray(t, jnp.float32)
            with trace_span("optim.ooc_probe", cat="optim",
                            chunks=len(z_chunks)):
                return sum(
                    k_probe_at_t(z, zd, t, labels[i], weights[i])
                    for i, (z, zd) in enumerate(zip(z_chunks, zd_chunks))
                )

        def stream_grad(z_chunks):
            f = jnp.zeros((), jnp.float32)
            g = jnp.zeros((data.dim,), jnp.float32)
            for i, (ci, cv) in enumerate(ell_feed()):
                with trace_span("optim.ooc_grad_chunk", cat="optim",
                                chunk=i):
                    fc, gc = k_grad(z_chunks[i], labels[i], weights[i],
                                    ci, cv)
                    f, g = f + fc, g + gc
            return f, g

        return (put_rep, stream_scores, data_value, data_value_at_t,
                stream_grad)

    def _ckpt_tag(self, data: ChunkedGLMData, prefix: str,
                  extra: str = "") -> str:
        """Fingerprint guarding a checkpoint against a DIFFERENT problem or
        data resuming from it: loss (task), shape, chunking, regularization
        (weights AND mask, ``extra`` carries solver-specific terms like the
        L1 weight), iteration cap, plus cheap content probes over EVERY
        data component (labels, weights, offsets, features of the first
        chunk) so same-shaped different data never cross-resumes —
        regenerated features or reweighted rows change the tag even when
        labels don't."""
        cfg = self.config
        c0 = data.chunks[0]
        data_probe = (
            float(np.asarray(data.labels[0], np.float64).sum()),
            float(np.asarray(data.weights[0], np.float64).sum()),
            float(np.asarray(data.offsets[0], np.float64).sum()),
            int(np.asarray(c0.idx, np.int64).sum()),
            float(np.asarray(c0.val, np.float64).sum()),
        )
        mask_probe = (
            "none" if self.reg_mask is None
            else repr(float(np.asarray(self.reg_mask, np.float64).sum()))
        )
        return (
            f"{prefix}:{type(self.loss).__name__}:{data.n_rows}:{data.dim}:"
            f"{data.n_chunks}:{data.chunk_rows}:{self.l2_weight}:{extra}"
            f"{mask_probe}:{cfg.history_length}:{cfg.max_iterations}:"
            f"{data_probe!r}"
        )

    @staticmethod
    def _restore(state, put_rep):
        """Checkpointed coefficient-space state, re-placed under the SAME
        replicated sharding the fresh path gives it — resuming a mesh solve
        with default-device arrays would recompile every kernel under
        different input shardings (and fail outright on a multi-host mesh
        with non-addressable devices)."""
        hist = LBFGSHistory(
            s=put_rep(jnp.asarray(state["hist_s"])),
            y=put_rep(jnp.asarray(state["hist_y"])),
            rho=put_rep(jnp.asarray(state["hist_rho"])),
            count=put_rep(jnp.asarray(state["hist_count"])),
            pos=put_rep(jnp.asarray(state["hist_pos"])),
        )
        return (
            put_rep(jnp.asarray(state["w"])),
            put_rep(jnp.asarray(state["g"])),
            hist,
            int(state["it"]),
            int(state["passes"]),
            jnp.asarray(state["f"]),
            jnp.asarray(state["f_prev"]),
            jnp.asarray(state["gnorm0"]),
            np.asarray(state["values"]).copy(),
            np.asarray(state["grad_norms"]).copy(),
        )

    def _l2_vec(self, w: Array) -> Array:
        if self.reg_mask is None:
            return jnp.full_like(w, self.l2_weight)
        return self.l2_weight * self.reg_mask.astype(w.dtype)

    # -- checkpoint/resume -------------------------------------------------

    _STATE_KEYS = ("w", "g", "hist_s", "hist_y", "hist_rho", "hist_count",
                   "hist_pos", "it", "passes", "f", "f_prev", "gnorm0",
                   "values", "grad_norms")

    def _load_checkpoint(self, tag: str, dim: int):
        if self.checkpoint_path is None:
            return None
        try:
            state = np.load(self.checkpoint_path, allow_pickle=False)
            # Validate AND materialize every member inside the try: a
            # corrupt zip can raise lazily on member access (BadZipFile /
            # EOFError / KeyError), and a bad checkpoint must mean "start
            # fresh", never a crashed solve that dies identically every
            # retry window.
            if str(state.get("tag", "")) != tag or state["w"].shape != (dim,):
                return None  # different problem/data: never cross-resume
            return {k: np.asarray(state[k]) for k in self._STATE_KEYS}
        except FileNotFoundError:
            return None  # no checkpoint yet: the normal first-run case
        except Exception as e:  # noqa: BLE001 - any unreadable state = fresh run
            # WARN, don't raise: a corrupt checkpoint means "start fresh".
            # But silence would make a RECURRING failure (e.g. permissions
            # on checkpoint_path) look like "no checkpoint" forever — every
            # recovery window would restart at iteration 0 with no signal.
            import logging

            logging.getLogger("photon_tpu.ooc").warning(
                "checkpoint %s unreadable (%s: %s) — starting fresh; if "
                "this repeats, resume is broken, not absent",
                self.checkpoint_path, type(e).__name__, e,
            )
            return None

    def _save_checkpoint(self, tag: str, w, g, hist, it, passes, f, f_prev,
                         gnorm0, values, grad_norms) -> None:
        if self.checkpoint_path is None:
            return
        tmp = self.checkpoint_path + ".tmp"
        try:
            with open(tmp, "wb") as fh:
                np.savez(
                    fh, tag=tag,
                    w=np.asarray(w), g=np.asarray(g),
                    hist_s=np.asarray(hist.s), hist_y=np.asarray(hist.y),
                    hist_rho=np.asarray(hist.rho),
                    hist_count=np.asarray(hist.count),
                    hist_pos=np.asarray(hist.pos),
                    it=it, passes=passes,
                    f=np.asarray(f), f_prev=np.asarray(f_prev),
                    gnorm0=np.asarray(gnorm0),
                    values=values, grad_norms=grad_norms,
                )
            os.replace(tmp, self.checkpoint_path)
        except OSError:
            pass  # best-effort: a failed save must never kill the solve

    def _primed_init(self, primed, data: ChunkedGLMData, w) -> Optional[tuple]:
        """(z, fd, gd) from a :class:`StreamPrimer` when it is usable for
        THIS solve: the prime's pass ran over EXACTLY these chunk objects
        (identity-checked — a prime from a different dataset, or from
        chunks replaced by a mid-stream regrow, must never be trusted), at
        exactly this start point, no mesh (the primer's margins are
        unsharded). Unusable primes fall back to the fresh init passes —
        correctness never depends on the pipeline.
        """
        if primed is None or self.mesh is not None:
            return None
        z = primed.get("z") or []
        chunks = primed.get("chunks") or []
        if len(z) != data.n_chunks or len(chunks) != data.n_chunks or any(
                a is not b for a, b in zip(chunks, data.chunks)):
            return None
        w0 = primed.get("w0")
        if w0 is None or w0.shape != w.shape or not bool(
                jnp.all(w0 == w)):
            return None
        return z, primed["fd"], primed["gd"]

    def optimize(self, data: ChunkedGLMData, x0: Array,
                 primed: Optional[dict] = None) -> OptimizerResult:
        """``primed`` (from :class:`StreamPrimer`) carries the init pass
        computed while the data streamed in; a valid prime skips the two
        init passes (scores + gradient) bit-identically.

        In-run device-loss recovery (docs/robustness.md): a classified
        device loss mid-solve does NOT kill the attempt — the executable
        caches clear, sweep-cache pins release, and the solve re-enters
        through ``_optimize_impl``, whose checkpoint load fast-forwards to
        the last saved iteration (or restarts the deterministic loop from
        scratch without a checkpoint path) — bit-identical either way.
        Bounded by ``PHOTON_DEVICE_LOST_MAX_RECOVERIES``; past it the
        error escalates to the supervisor restart.

        An ``oom``-classified failure takes the DEGRADATION ladder instead
        (docs/robustness.md §"Memory pressure"): restarting with identical
        chunk shapes would deterministically re-OOM, so the solve halves
        ``chunk_rows`` (``ChunkedGLMData.rechunk``) and re-enters — the
        per-chunk device footprint halves while the row content (weight-0
        ghost padding) is unchanged. Bounded by
        ``PHOTON_OOM_MAX_DOWNSHIFTS``; the downshift is journaled, counted
        in ``oom_downshifts_total{site="optim.ooc_chunk"}``, and sticky
        for this solve (the re-cut data IS the new plan). Note the
        rechunked solve restarts its iteration loop from scratch: the
        checkpoint tag covers the chunking, so a cross-chunking resume is
        refused by design."""
        recoveries = 0
        while True:
            try:
                return self._optimize_impl(data, x0, primed=primed)
            except Exception as e:  # noqa: BLE001 - classified below
                import logging

                from photon_tpu.runtime import backend_guard as _bg
                from photon_tpu.runtime import memory_guard as _mg

                log = logging.getLogger("photon_tpu.ooc")
                if _mg.is_oom(e):
                    # Rechunking under a mesh must keep chunk_rows evenly
                    # divisible over the data axis (_mesh_puts contract).
                    new_rows = -(-data.chunk_rows // 2)
                    divisible = (self.mesh is None or new_rows
                                 % self.mesh.shape[self.data_axis] == 0)
                    if data.chunk_rows <= 1 or not divisible:
                        # No cheaper cut exists: journal the classified
                        # exhaustion (same contract as re.solve) so the
                        # recovery record shows WHY the OOM escalated.
                        _mg.journal_event(
                            "oom_exhausted", site="optim.ooc_chunk",
                            cause="oom",
                            plan=f"chunk_rows={data.chunk_rows}",
                            reason=("chunk_rows already 1" if divisible
                                    else "half-cut not divisible over the "
                                         "mesh data axis"))
                        raise
                    if not _mg.downshifter("optim.ooc_chunk").absorb(
                            e, before=f"chunk_rows={data.chunk_rows}",
                            after=f"chunk_rows={new_rows}"):
                        raise  # absorb journaled the spent budget
                    if self.device_cache is not None:
                        # The old cut's pins can never be hit again.
                        for c in data.chunks:
                            self.device_cache.discard(
                                ("ooc_ell", id(c.idx)))
                    data = data.rechunk(2)
                    primed = None  # margins were cut for the old shape
                    continue
                if (not _bg.is_device_lost(e)
                        or recoveries >= _bg.max_inrun_recoveries()):
                    raise
                recoveries += 1
                log.warning(
                    "device lost mid-solve (%s: %s); in-run recovery %d/%d"
                    "%s", type(e).__name__, e, recoveries,
                    _bg.max_inrun_recoveries(),
                    ", resuming from checkpoint" if self.checkpoint_path
                    else ", re-running the deterministic loop")
                _bg.recover_from_device_loss(
                    "out-of-core solve", device_cache=self.device_cache,
                )
                # The prime's resident margins died with the device; the
                # re-entry rebuilds them (checkpoint scores-rebuild pass or
                # fresh init passes).
                primed = None

    def _optimize_impl(self, data: ChunkedGLMData, x0: Array,
                       primed: Optional[dict] = None) -> OptimizerResult:
        cfg = self.config
        dim = data.dim
        (put_rep, stream_scores, data_value, data_value_at_t,
         stream_grad) = self._streams(data)

        w = put_rep(jnp.asarray(x0, jnp.float32))
        l2v = self._l2_vec(w)

        def full_fg(wv, z_chunks):
            fd, gd = stream_grad(z_chunks)
            return (fd + 0.5 * jnp.sum(l2v * wv * wv), gd + l2v * wv)

        max_it = cfg.max_iterations
        ckpt_tag = self._ckpt_tag(data, "ooc-v1")
        state = self._load_checkpoint(ckpt_tag, dim)
        if state is not None:
            (w, g, hist, it, passes, f, f_prev, gnorm0, values,
             grad_norms) = self._restore(state, put_rep)
            z = stream_scores(w)  # scores rebuild from w: one pass
            passes += 1
        else:
            prime = self._primed_init(primed, data, w)
            if prime is not None:
                # The init already ran during ingest as ONE fused pass per
                # chunk (scores + grad off the same feed) — data_passes is
                # a measured count, so the prime records 1, not the
                # unprimed path's 2.
                z, fd, gd = prime
                f = fd + 0.5 * jnp.sum(l2v * w * w)
                g = gd + l2v * w
                passes = 1
            else:
                # init: one scores pass + one grad pass
                z = stream_scores(w)
                f, g = full_fg(w, z)
                passes = 2
            gnorm0 = jnp.linalg.norm(g)
            hist = empty_history(cfg.history_length, dim, jnp.float32)
            values = np.full(max_it + 1, np.inf, np.float32)
            grad_norms = np.full(max_it + 1, np.inf, np.float32)
            values[0] = float(f)
            grad_norms[0] = float(gnorm0)
            it = 0
            f_prev = jnp.asarray(jnp.inf, jnp.float32)

        reason = NOT_CONVERGED
        last_save = float("-inf")
        while True:
            # Chaos hook: error="device_lost" here exercises the in-run
            # recovery wrapper in optimize() (checkpoint fast-forward →
            # bit-identical result).
            fault_point("optim.ooc_iteration", it=it)
            # Convergence test BEFORE the max-iteration cut (and so also
            # after the final update) — same ordering as the in-core loop,
            # so converged_reason agrees on runs that converge exactly at
            # the iteration cap.
            reason = int(check_convergence(
                jnp.asarray(it), f_prev, f, jnp.linalg.norm(g), gnorm0, cfg
            ))
            if reason != NOT_CONVERGED:
                break
            if it >= max_it:
                reason = MAX_ITERATIONS
                break
            d = two_loop_direction(g, hist)
            dg = jnp.dot(d, g)
            if float(dg) >= 0.0:  # not a descent direction: restart memory
                hist = empty_history(cfg.history_length, dim, jnp.float32)
                d, dg = -g, -jnp.dot(g, g)
            zd = stream_scores(d, with_offsets=False)
            passes += 1
            # Armijo backtracking over RESIDENT margins (no data pass per
            # probe) — same constants as optim/lbfgs.py armijo_backtrack.
            t, ft, accept = 1.0, f, False
            t_last = 0.0  # the step size the CURRENT ft was evaluated at
            c1, shrink = 1e-4, 0.5
            for _ in range(cfg.max_line_search_iterations):
                ft = data_value_at_t(z, zd, t) + _reg_at_t(
                    w, d, jnp.asarray(t, jnp.float32), l2v
                )
                if bool(jnp.isfinite(ft)) and float(ft) <= float(
                    f + c1 * t * dg
                ):
                    accept = True
                    break
                t_last = t
                t *= shrink
            if not accept and bool(jnp.isfinite(ft)) and float(ft) < float(f):
                # Smallest PROBED step still decreases f: apply that exact
                # step, not the once-more-shrunk t that was never evaluated.
                t = t_last
                accept = t > 0.0
            if not accept:
                # No further progress possible — same terminal behavior as
                # the in-core loop (next dual test fires on |Δf| = 0).
                reason = FUNCTION_VALUES_CONVERGED
                break
            s = t * d
            w = w + s
            z = [z[i] + t * zd[i] for i in range(data.n_chunks)]
            f_prev = f
            f, g_new = full_fg(w, z)
            passes += 1
            hist = update_history(hist, s, g_new - g)
            g = g_new
            it += 1
            values[it] = float(f)
            grad_norms[it] = float(jnp.linalg.norm(g))
            # Save BEFORE the progress callback: the checkpoint must bank
            # the just-finished iteration even if logging (or a supervisor
            # signal delivered inside it) kills the process. Throttled
            # after the first save (see checkpoint_min_interval_s).
            now = time.monotonic()
            if it == 1 or now - last_save >= self.checkpoint_min_interval_s:
                self._save_checkpoint(ckpt_tag, w, g, hist, it, passes, f,
                                      f_prev, gnorm0, values, grad_norms)
                last_save = now
            if self.progress is not None:
                self.progress(it, values[it], grad_norms[it], passes)

        self._save_checkpoint(ckpt_tag, w, g, hist, it, passes, f,
                              f_prev, gnorm0, values, grad_norms)
        return OptimizerResult(
            x=w,
            value=f,
            grad_norm=jnp.linalg.norm(g),
            iterations=jnp.asarray(it, jnp.int32),
            converged_reason=jnp.asarray(reason, jnp.int32),
            values=jnp.asarray(values),
            grad_norms=jnp.asarray(grad_norms),
            data_passes=jnp.asarray(passes, jnp.int32),
        )


@dataclasses.dataclass(frozen=True)
class OutOfCoreOWLQN(OutOfCoreLBFGS):
    """Host-loop OWL-QN over a :class:`ChunkedGLMData` — L1/elastic-net at
    beyond-HBM scale (BASELINE config 2; SURVEY.md §2.1 OWL-QN).

    Same Andrew & Gao (2007) semantics as the in-core ``optim/owlqn.py``
    (pseudo-gradient, smooth-gradient history, direction alignment, orthant
    projection of trial points, Armijo on the total objective via the
    projected displacement, same constants), so in-core and out-of-core
    solves agree to numerical noise — tested.

    The one structural difference from :class:`OutOfCoreLBFGS`: the orthant
    projection makes a trial point a NONLINEAR function of the step size
    (clipped coordinates pin to zero), so the resident direction margins
    ``zd`` cannot price a probe — each line-search probe streams one scores
    pass. Probes are value-only (the in-core path computes a fused
    value+grad per probe = 2 passes), so a typical accept-at-t=1 iteration
    costs probe + gradient = 2 streamed passes, identical to the smooth
    solver. Everything else (mesh row-sharding, per-iteration checkpoints,
    λ-sweep kernel reuse) is inherited.

    ``l1_weight`` scales ``reg_mask`` (ones if absent) into the
    per-coefficient L1 vector — the intercept stays unpenalized exactly as
    in-core ``GLMOptimizationProblem.run`` builds ``l1 * mask``.
    """

    l1_weight: float = 0.0

    def _l1_vec(self, w: Array) -> Array:
        if self.reg_mask is None:
            return jnp.full_like(w, self.l1_weight)
        return self.l1_weight * self.reg_mask.astype(w.dtype)

    def _optimize_impl(self, data: ChunkedGLMData, x0: Array,
                       primed: Optional[dict] = None) -> OptimizerResult:
        cfg = self.config
        dim = data.dim
        (put_rep, stream_scores, data_value, data_value_at_t,
         stream_grad) = self._streams(data)

        w = put_rep(jnp.asarray(x0, jnp.float32))
        l2v = self._l2_vec(w)
        l1v = self._l1_vec(w)

        def total_at(wv, z_chunks):
            """Total objective (data + L2 + L1) from resident margins."""
            return (
                data_value(z_chunks)
                + 0.5 * jnp.sum(l2v * wv * wv)
                + jnp.sum(l1v * jnp.abs(wv))
            )

        def smooth_fg(wv, z_chunks):
            """Fused (total objective, SMOOTH gradient) — one streamed
            pass. History and pseudo-gradient both want the smooth grad
            (data + L2), per Andrew & Gao."""
            fd, gd = stream_grad(z_chunks)
            f = (fd + 0.5 * jnp.sum(l2v * wv * wv)
                 + jnp.sum(l1v * jnp.abs(wv)))
            return f, gd + l2v * wv

        max_it = cfg.max_iterations
        ckpt_tag = self._ckpt_tag(
            data, "ooc-owlqn-v1", extra=f"{self.l1_weight}:"
        )
        state = self._load_checkpoint(ckpt_tag, dim)
        if state is not None:
            (w, g, hist, it, passes, f, f_prev, gnorm0, values,
             grad_norms) = self._restore(state, put_rep)
            z = stream_scores(w)  # scores rebuild from w: one pass
            passes += 1
        else:
            prime = self._primed_init(primed, data, w)
            if prime is not None:
                z, fd, gd = prime
                f = (fd + 0.5 * jnp.sum(l2v * w * w)
                     + jnp.sum(l1v * jnp.abs(w)))
                g = gd + l2v * w
                passes = 1  # one fused streamed pass during ingest
            else:
                z = stream_scores(w)
                f, g = smooth_fg(w, z)
                passes = 2
            gnorm0 = jnp.linalg.norm(pseudo_gradient(w, g, l1v))
            hist = empty_history(cfg.history_length, dim, jnp.float32)
            values = np.full(max_it + 1, np.inf, np.float32)
            grad_norms = np.full(max_it + 1, np.inf, np.float32)
            values[0] = float(f)
            grad_norms[0] = float(gnorm0)
            it = 0
            f_prev = jnp.asarray(jnp.inf, jnp.float32)

        reason = NOT_CONVERGED
        last_save = float("-inf")
        while True:
            # Same in-run device-loss recovery hook as the smooth solver.
            fault_point("optim.ooc_iteration", it=it)
            pg = pseudo_gradient(w, g, l1v)
            reason = int(check_convergence(
                jnp.asarray(it), f_prev, f, jnp.linalg.norm(pg), gnorm0, cfg
            ))
            if reason != NOT_CONVERGED:
                break
            if it >= max_it:
                reason = MAX_ITERATIONS
                break
            d = two_loop_direction(pg, hist)
            # Orthant alignment: zero components disagreeing with -pg;
            # steepest descent if alignment annihilated the direction.
            d = jnp.where(d * (-pg) > 0.0, d, 0.0)
            if float(jnp.dot(d, d)) == 0.0:
                d = -pg
            xi = orthant(w, pg)

            # Backtracking Armijo on the TOTAL objective with orthant
            # projection of each trial point — one streamed scores pass
            # per probe (see class doc). Same constants as in-core.
            t, accept = 1.0, False
            xt = w
            zt = z
            ft = f
            for _ in range(cfg.max_line_search_iterations):
                xt = jnp.where((w + t * d) * xi >= 0.0, w + t * d, 0.0)
                zt = stream_scores(xt)
                passes += 1
                ft = total_at(xt, zt)
                decrease = jnp.dot(pg, xt - w)
                if bool(jnp.isfinite(ft)) and float(ft) <= float(
                    f + 1e-4 * decrease
                ):
                    accept = True
                    break
                t *= 0.5
            if not accept and bool(jnp.isfinite(ft)) and float(ft) < float(f):
                accept = True  # smallest probed step still decreases f
            if not accept:
                reason = FUNCTION_VALUES_CONVERGED
                break
            s = xt - w
            w = xt
            z = zt
            f_prev = f
            f, g_new = smooth_fg(w, z)
            passes += 1
            hist = update_history(hist, s, g_new - g)
            g = g_new
            it += 1
            values[it] = float(f)
            grad_norms[it] = float(
                jnp.linalg.norm(pseudo_gradient(w, g, l1v))
            )
            now = time.monotonic()
            if it == 1 or now - last_save >= self.checkpoint_min_interval_s:
                self._save_checkpoint(ckpt_tag, w, g, hist, it, passes, f,
                                      f_prev, gnorm0, values, grad_norms)
                last_save = now
            if self.progress is not None:
                self.progress(it, values[it], grad_norms[it], passes)

        self._save_checkpoint(ckpt_tag, w, g, hist, it, passes, f,
                              f_prev, gnorm0, values, grad_norms)
        return OptimizerResult(
            x=w,
            value=f,
            grad_norm=jnp.linalg.norm(pseudo_gradient(w, g, l1v)),
            iterations=jnp.asarray(it, jnp.int32),
            converged_reason=jnp.asarray(reason, jnp.int32),
            values=jnp.asarray(values),
            grad_norms=jnp.asarray(grad_norms),
            data_passes=jnp.asarray(passes, jnp.int32),
        )


def scores_out_of_core(data: ChunkedGLMData, w) -> np.ndarray:
    """Streamed scores z = Xw + offsets for every (true) row — the chunked
    analogue of ``GeneralizedLinearModel.compute_score``. Reuses the cached
    matvec kernel, so a λ-sweep scoring after each fit never recompiles."""
    w = jnp.asarray(w, jnp.float32)
    k_matvec = _matvec_for(data.dim)
    outs = [
        np.asarray(k_matvec(w, c.idx, c.val, data.offsets[i]))
        for i, c in enumerate(data.chunks)
    ]
    return np.concatenate(outs)[: data.n_rows]


def run_out_of_core(problem, data: ChunkedGLMData, w0=None, reg_mask=None,
                    progress=None, checkpoint_path=None, mesh=None,
                    data_axis="data", device_cache=None, primed=None,
                    collectives="gspmd"):
    """Problem-level entry mirroring ``GLMOptimizationProblem.run`` for the
    out-of-core path: same task→loss mapping, regularization/reg-mask
    semantics, and ``(GLMModel, OptimizerResult)`` return. LBFGS handles
    smooth L2; OWLQN handles any L1 component (L1/ELASTIC_NET) — the same
    optimizer↔regularization pairing rules as in-core run(): an L1
    component under a smooth optimizer raises (silently training the L2
    part alone would return wrong coefficients). Variance NONE only
    (SIMPLE/FULL need in-core Hessian passes)."""
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.glm import GeneralizedLinearModel
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.optim import OptimizerType

    l1 = problem.regularization.l1_weight(float(problem.reg_weight))
    common = dict(
        loss=loss_for_task(problem.task),
        l2_weight=problem.regularization.l2_weight(float(problem.reg_weight)),
        reg_mask=reg_mask,
        config=problem.optimizer_config,
        progress=progress,
        checkpoint_path=checkpoint_path,
        mesh=mesh,
        data_axis=data_axis,
        collectives=collectives,
        device_cache=device_cache,
    )
    if problem.optimizer_type == OptimizerType.OWLQN:
        solver = OutOfCoreOWLQN(l1_weight=l1, **common)
    elif problem.optimizer_type != OptimizerType.LBFGS:
        raise NotImplementedError(
            "out-of-core training supports LBFGS (smooth L2) and OWLQN "
            f"(L1/elastic-net) only; got {problem.optimizer_type}"
        )
    elif l1 > 0.0:
        raise NotImplementedError(
            "L1 components need an orthant-wise optimizer: use "
            "OptimizerType.OWLQN out-of-core, same as the in-core rule; "
            f"got LBFGS with {problem.regularization.reg_type.name}"
        )
    else:
        solver = OutOfCoreLBFGS(**common)
    if w0 is None:
        w0 = jnp.zeros((data.dim,), jnp.float32)
    result = solver.optimize(data, w0, primed=primed)
    model = GeneralizedLinearModel(
        Coefficients(means=result.x, variances=None), problem.task
    )
    return model, result
