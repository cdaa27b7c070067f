"""Batched training data as fixed-shape device pytrees.

Parity: reference ⟦photon-api/.../data/GameDatum.scala⟧ / ``LabeledPoint(label,
features, offset, weight)`` — but instead of an RDD of per-example records,
data lives as structure-of-arrays batches with **static shapes**, the form XLA
tiles onto the MXU (SURVEY.md §7 design stance).

Feature representations:

* ``DenseFeatures`` — ``x[N, D]``; right for small/mid feature spaces where the
  score is one big matmul.
* ``SparseFeatures`` — padded ELL format: ``idx[N, K] int32`` / ``val[N, K]``
  with K = max nnz per row; padding slots point at column ``D`` (a zero
  "ghost" column) with value 0. This is the TPU-native replacement for the
  reference's Breeze ``SparseVector`` rows: gathers/segment-sums over fixed
  [N, K] tiles instead of per-row pointer chasing, so a 10M-feature space
  never materializes densely (SURVEY.md §7 "hard parts" #2).

Both support ``matvec`` (scores), ``rmatvec`` (gradient accumulation — the
transpose action), and ``sq_rmatvec`` (Hessian-diagonal accumulation).
Autodiff of ``matvec`` produces exactly ``rmatvec`` (gather ↔ scatter-add), so
objectives can be plain differentiated functions.

A ``padded_rows`` mask supports static-shape batching: rows beyond the true
sample count carry weight 0 and contribute nothing (the equivalent of the
reference's per-partition iteration just not seeing absent rows).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Union

import jax
import jax.numpy as jnp

from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.obs.trace import device_wait, trace_span
from photon_tpu.ops import pass_counter
from photon_tpu.types import REAL_ACCELERATOR_BACKENDS

Array = jax.Array

# The tables cost at most 20 B an entry on the device beside the ELL arrays'
# 8 B (``window``: 8 B a slot in each of two tables; ``planes``, of ``X.w``
# alone: 8 B an entry; ``fast``: 20 B);
# ``SparseFeatures.with_accelerator_paths`` attaches none over this. Which of
# the two an op gets is ``ops/fast_sparse.py`` ``WINDOW_BREAK_EVEN_PASSES``.
ACCEL_TABLE_BUDGET_BYTES = 4e9


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseFeatures:
    """Row-major dense design matrix ``x[N, D]``."""

    x: Array

    @property
    def n_rows(self) -> int:
        return self.x.shape[0]

    @property
    def dim(self) -> int:
        return self.x.shape[1]

    @jax.named_scope("sparse.matvec")
    def matvec(self, w: Array) -> Array:
        pass_counter.record("matvec")
        return self.x @ w

    @jax.named_scope("sparse.rmatvec")
    def rmatvec(self, v: Array) -> Array:
        """Xᵀv — accumulate per-row coefficients ``v`` into feature space."""
        pass_counter.record("rmatvec")
        return self.x.T @ v

    @jax.named_scope("sparse.sq_rmatvec")
    def sq_rmatvec(self, v: Array) -> Array:
        """(X∘X)ᵀv — for Hessian diagonals: Σᵢ vᵢ·xᵢⱼ²."""
        pass_counter.record("sq_rmatvec")
        return (self.x * self.x).T @ v

    def row_slice(self, start: int, size: int) -> "DenseFeatures":
        return DenseFeatures(jax.lax.dynamic_slice_in_dim(self.x, start, size, 0))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseFeatures:
    """Padded ELL sparse matrix: per-row index/value lists of width K.

    ``idx[N, K]`` holds column ids in [0, D]; id == D marks padding (its value
    must be 0). ``dim`` (static) is the true feature dimension D.

    ``fast`` (optional) is the one table object the features may carry
    (``ops/fast_sparse.py`` ``FastSparseAux``: a table for ``X.w`` and one
    for ``X^T.r``), and the one seam of the sparse pass. Each op runs what
    its table is: ``window`` (a ``WindowTable``: the windowed one-hot Pallas
    kernel ``gather_reduce``, float32, nothing of the entries' length
    written to HBM), for ``X.w`` on a tall narrow matrix ``planes`` (a
    ``PlaneTable``: the same lookup over the ELL columns as they lie, no
    sort) or ``fast`` (a row-slice table: row-slice gather +
    one-hot reduce, what the build keeps for an op whose entries do not sort
    into narrow windows); and the ``plain`` one when no table is attached
    (gather / ``segment_sum``, inline below; also the reference the tests
    hold the others to, and what a ``window`` table's op runs on an operand
    that is not float32). ``with_accelerator_paths`` decides whether to
    attach tables; ``with_fast_path()`` attaches them whatever the platform;
    ``build_fast_aux`` chooses each op's formulation from the table it built.
    """

    idx: Array
    val: Array
    dim: int = dataclasses.field(metadata=dict(static=True))
    fast: Optional[object] = None

    @property
    def n_rows(self) -> int:
        return self.idx.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.idx.shape[1]

    def with_fast_path(self, q_capacity: int = 2048) -> "SparseFeatures":
        """Build the fast-path layouts (host-side, once) and attach them."""
        from photon_tpu.ops.fast_sparse import build_fast_aux

        if self.fast is not None:
            return self
        with device_wait("accel_tables"):
            idx, val = jax.device_get(self.idx), jax.device_get(self.val)
        aux = build_fast_aux(idx, val, self.dim, q_capacity=q_capacity)
        if jnp.dtype(self.val.dtype).itemsize < 4:
            # Values were already narrowed (with_value_dtype before attach):
            # the tables' values must match or their half of the bandwidth
            # saving silently evaporates (builder emits f32).
            # Only narrow-dtype casts: f64 runs keep the f32 tables (the
            # builder already truncated through f32, so widening would
            # double their memory for zero precision).
            aux = aux.cast_values(self.val.dtype)
        return dataclasses.replace(self, fast=aux)

    def with_accelerator_paths(self) -> "SparseFeatures":
        """The one place that decides which formulation a program holds:
        attach the ``fast`` tables (``with_fast_path``) on an accelerator
        backend, when the tables fit the byte budget; else return the
        features as they are, on ``plain``. Off the accelerator XLA's CPU
        lowerings of ``plain`` beat the ``fast`` formulation and the
        host-side table build is pure overhead. The third thing the code
        observes, a mesh, is seen where the rows are distributed:
        ``parallel/mesh.py`` ``strip_unshardable_aux`` takes the tables off
        again, because the tables do not shard by rows. The
        estimator, the transformer and the training driver call this, so
        no caller knows about layouts."""
        if jax.default_backend() not in REAL_ACCELERATOR_BACKENDS:
            return self
        if os.environ.get("PHOTON_DISABLE_ACCEL_PATHS") == "1":
            # The ``plain`` reference on a chip: ``chip_smoke.py --chips 4``
            # sets it on the one-device side of its comparison, whose other
            # side is a mesh and so on ``plain`` too.
            return self
        entries = int(self.idx.shape[0]) * int(self.idx.shape[1])
        if 20 * entries > ACCEL_TABLE_BUDGET_BYTES:
            return self
        vd = os.environ.get("PHOTON_VALUE_DTYPE")
        if vd is not None and jnp.dtype(vd) != jnp.dtype(self.val.dtype):
            # Opt-in narrow value storage (e.g. PHOTON_VALUE_DTYPE=bfloat16;
            # see with_value_dtype). Tables build in f32 first, then storage
            # casts.
            return self.with_fast_path().with_value_dtype(vd)
        return self.with_fast_path()

    def with_value_dtype(self, dtype) -> "SparseFeatures":
        """Store feature VALUES in a narrower dtype (e.g. ``jnp.bfloat16``).

        The ops upcast on load and accumulate in the operand precision, so
        only storage narrows: 2 B of the 4 B a stored value takes, in ``val``
        and in the tables, which are re-cast to match. What that buys
        a fit on the chip is not measured (the benchmark uses this path as
        its failing control, PERF.md §2). One-hot / binary / small-integer
        features are EXACT in bfloat16; continuous features round to 8
        mantissa bits — opting in accepts that quantization.
        """
        dt = jnp.dtype(dtype)
        if jnp.dtype(self.val.dtype) == dt:
            return self
        out = dataclasses.replace(self, val=self.val.astype(dt))
        if out.fast is not None:
            out = dataclasses.replace(out, fast=out.fast.cast_values(dt))
        return out

    def without_fast_path(self) -> "SparseFeatures":
        """Drop the tables (e.g. before row-sharding: a table sorted by
        column or by window is not partitionable along the row axis)."""
        if self.fast is None:
            return self
        return dataclasses.replace(self, fast=None)

    def _formulation(self, op: str, operand: Array) -> str:
        """Which formulation ``op`` puts into the program being traced
        here: what the op's table is (``"window"``, ``"planes"`` or
        ``"fast"``) when the tables are attached, else ``"plain"``;
        ``"plain"`` also for an operand the float32 kernel of ``window``
        and ``planes`` cannot take.
        Counted once per trace (or eager call) in
        ``sparse_op_traces_total{op, formulation}``, so a run can say what
        its programs really hold, not what a look-alike batch would get."""
        pass_counter.record(op)
        kind = "plain" if self.fast is None else self.fast.formulation(op)
        if kind in ("window", "planes") and operand.dtype != jnp.float32:
            kind = "plain"
        REGISTRY.counter(
            "sparse_op_traces_total",
            "sparse feature ops traced into programs, by formulation",
        ).inc(op=op, formulation=kind)
        return kind

    @jax.named_scope("sparse.matvec")
    def matvec(self, w: Array) -> Array:
        if self._formulation("matvec", w) != "plain":
            from photon_tpu.ops.fast_sparse import matvec_fast

            return matvec_fast(self.fast, self.val, w, self.dim)
        # Gather through an extended vector with a zero ghost column so
        # padding indices read 0 — no masking needed in the hot loop.
        w_ext = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        return jnp.sum(w_ext[self.idx] * self.val, axis=-1)

    @jax.named_scope("sparse.rmatvec")
    def rmatvec(self, v: Array) -> Array:
        if self._formulation("rmatvec", v) != "plain":
            from photon_tpu.ops.fast_sparse import rmatvec_fast

            return rmatvec_fast(self.fast, v, self.dim)
        contrib = (v[:, None] * self.val).ravel()
        out = jax.ops.segment_sum(
            contrib, self.idx.ravel(), num_segments=self.dim + 1
        )
        return out[: self.dim]

    @jax.named_scope("sparse.sq_rmatvec")
    def sq_rmatvec(self, v: Array) -> Array:
        if self._formulation("sq_rmatvec", v) != "plain":
            from photon_tpu.ops.fast_sparse import rmatvec_fast

            return rmatvec_fast(self.fast, v, self.dim, square_vals=True)
        contrib = (v[:, None] * self.val * self.val).ravel()
        out = jax.ops.segment_sum(
            contrib, self.idx.ravel(), num_segments=self.dim + 1
        )
        return out[: self.dim]

    def row_slice(self, start: int, size: int) -> "SparseFeatures":
        return SparseFeatures(
            idx=jax.lax.dynamic_slice_in_dim(self.idx, start, size, 0),
            val=jax.lax.dynamic_slice_in_dim(self.val, start, size, 0),
            dim=self.dim,
        )


Features = Union[DenseFeatures, SparseFeatures]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LabeledBatch:
    """A batch of labeled examples: the SoA form of the reference's
    ``RDD[(UniqueSampleId, LabeledPoint)]`` for one feature shard.

    ``weights`` doubles as the validity mask: padded rows carry weight 0.
    """

    features: Features
    labels: Array               # [N]
    offsets: Array              # [N]
    weights: Array              # [N]

    @property
    def n_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def dim(self) -> int:
        return self.features.dim

    def with_offsets(self, offsets: Array) -> "LabeledBatch":
        return dataclasses.replace(self, offsets=offsets)

    def add_to_offsets(self, scores: Array) -> "LabeledBatch":
        return dataclasses.replace(self, offsets=self.offsets + scores)

    def with_accelerator_paths(self) -> "LabeledBatch":
        """Sparse features gain the tables of the sparse pass (see
        ``SparseFeatures.with_accelerator_paths``); dense features no-op.
        Every call that attaches is a host-side table build: a caller that
        comes back to the same features keeps the result beside them
        (``GameEstimator`` does, with its prepared bundle)."""
        feats = self.features
        if not hasattr(feats, "with_accelerator_paths"):
            return self
        # Around the call, not inside ops/fast_sparse.py: the span also
        # covers reading idx/val back and placing the tables, and it is a
        # span only of a build (off the accelerator, over the memory budget
        # or already attached, the features come back as they went in).
        with trace_span("data.accel_tables", cat="data",
                        entries=feats.idx.size, dim=feats.dim) as span:
            attached = feats.with_accelerator_paths()
            if attached is feats:
                span.discard()
            elif attached.fast is not None:
                span.set(**attached.fast.span_arguments())
        if attached is feats:
            return self
        return dataclasses.replace(self, features=attached)


def make_dense_batch(
    x,
    labels,
    offsets=None,
    weights=None,
    dtype=jnp.float32,
) -> LabeledBatch:
    x = jnp.asarray(x, dtype)
    n = x.shape[0]
    return LabeledBatch(
        features=DenseFeatures(x),
        labels=jnp.asarray(labels, dtype),
        offsets=jnp.zeros((n,), dtype) if offsets is None else jnp.asarray(offsets, dtype),
        weights=jnp.ones((n,), dtype) if weights is None else jnp.asarray(weights, dtype),
    )


def ell_from_rows(
    rows: list[tuple],
    dim: int,
    max_nnz: Optional[int] = None,
    dtype=jnp.float32,
) -> SparseFeatures:
    """Pack per-row (indices, values) pairs into padded ELL arrays (host-side)."""
    import numpy as np

    n = len(rows)
    k = max_nnz or max((len(r[0]) for r in rows), default=1)
    k = max(k, 1)
    idx = np.full((n, k), dim, dtype=np.int32)
    val = np.zeros((n, k), dtype=np.dtype(dtype))
    for i, (ri, rv) in enumerate(rows):
        if len(ri) > k:
            raise ValueError(
                f"row {i} has {len(ri)} nonzeros > max_nnz={k}; raise max_nnz "
                "(silent truncation would corrupt features)"
            )
        idx[i, : len(ri)] = np.asarray(ri)
        val[i, : len(rv)] = np.asarray(rv)
    return SparseFeatures(idx=jnp.asarray(idx), val=jnp.asarray(val, dtype), dim=dim)
