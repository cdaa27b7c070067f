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
from typing import Optional, Union

import jax
import jax.numpy as jnp

from photon_tpu.obs.metrics import REGISTRY
from photon_tpu.obs.trace import trace_span
from photon_tpu.ops import pass_counter
from photon_tpu.types import REAL_ACCELERATOR_BACKENDS

Array = jax.Array

_WARNED_PALLAS_F64 = False


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

    ``fast`` (optional, see ``ops/fast_sparse.py``) carries precomputed
    MXU-friendly layouts; when present, matvec/rmatvec take the fast path
    (row-slice gather + one-hot reduce) instead of XLA's slow generic
    gather/scatter lowering. Attach with ``with_fast_path()``.

    ``pallas`` (optional, see ``ops/pallas_sparse.py``) carries the Pallas
    slot tables; when attached, f32 matvec/rmatvec on a TPU backend run as
    the hand-written kernels, compiled — never interpreted. Attach only
    explicitly with ``with_pallas_path()`` (the TPU compiler does not accept
    the kernels today, so nothing attaches them by default); off-TPU the
    XLA paths are used unless ``PHOTON_PALLAS_INTERPRET=1`` sends the
    kernels through the Pallas interpreter (CPU tests only).
    """

    idx: Array
    val: Array
    dim: int = dataclasses.field(metadata=dict(static=True))
    fast: Optional[object] = None
    pallas: Optional[object] = None

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
        aux = build_fast_aux(
            jax.device_get(self.idx), jax.device_get(self.val), self.dim,
            q_capacity=q_capacity,
        )
        if jnp.dtype(self.val.dtype).itemsize < 4:
            # Values were already narrowed (with_value_dtype before attach):
            # the column table must match or the rmatvec half of the
            # bandwidth saving silently evaporates (builder emits f32).
            # Only narrow-dtype casts: f64 runs keep the f32 table (the
            # builder already truncated through f32, so widening would
            # double its memory for zero precision).
            aux = dataclasses.replace(
                aux, cs_val=aux.cs_val.astype(self.val.dtype)
            )
        return dataclasses.replace(self, fast=aux)

    def with_pallas_path(self) -> "SparseFeatures":
        """Build the Pallas slot tables (host-side, once) and attach them,
        plus the XLA fast path that serves off-TPU. Large datasets chunk
        (512K-row / 256K-feature table slices); no-op (XLA fast path only)
        if the packed tables would blow the device-memory budget."""
        from photon_tpu.ops.pallas_sparse import build_pallas_aux

        out = self.with_fast_path()
        if out.pallas is not None:
            return out
        try:
            aux = build_pallas_aux(
                jax.device_get(self.idx), jax.device_get(self.val), self.dim
            )
        except ValueError:  # over the table-memory budget
            return out
        return dataclasses.replace(out, pallas=aux)

    def with_accelerator_paths(self) -> "SparseFeatures":
        """Attach the MXU-friendly XLA layouts (``with_fast_path``) where
        they can actually win: accelerator backend + unsharded features
        (row-sharding drops them — the column-sorted tables are not
        partitionable along rows). The estimator/transformer call this so
        driver-trained models run the fast formulation on TPU without
        callers knowing about layouts; off-accelerator this is a no-op
        (XLA's plain CPU lowerings beat the fast-path formulations there,
        and the host-side table builds are pure overhead).

        The Pallas tables are never attached here: the TPU compiler refuses
        the kernels as written (``ops/pallas_sparse.py`` module doc, ROADMAP
        S1), and a default path must be one the chip accepts. They stay
        reachable through an explicit ``with_pallas_path()``."""
        import os

        import jax

        if jax.default_backend() not in REAL_ACCELERATOR_BACKENDS:
            return self
        if os.environ.get("PHOTON_DISABLE_ACCEL_PATHS") == "1":
            # Operator kill switch: the fast path's one-hot MXU program is
            # the heaviest compile of a fixed-effect solve. Disables every
            # AUTO-attach (drivers/estimators route through here); code
            # that calls with_fast_path()/with_pallas_path() explicitly —
            # e.g. bench.py's sparse race — honors the same variable at its
            # own call site, keeping explicit requests explicit.
            return self
        # HBM guard: the layouts cost ~20 bytes/entry on device on top of
        # the 8 bytes/entry ELL data. At config-5 scale (1.3e9 entries)
        # they would crowd out the batch itself; past the budget the solve
        # keeps the plain formulation (and P3/row sharding remain the
        # intended scale paths). Tunable: PHOTON_ACCEL_AUX_BUDGET_GB.
        entries = int(self.idx.shape[0]) * int(self.idx.shape[1])
        budget_gb = float(os.environ.get("PHOTON_ACCEL_AUX_BUDGET_GB", "4"))
        if 20 * entries > budget_gb * 1e9:
            return self
        vd = os.environ.get("PHOTON_VALUE_DTYPE")
        if vd is not None and jnp.dtype(vd) != jnp.dtype(self.val.dtype):
            # Opt-in narrow value storage (e.g. PHOTON_VALUE_DTYPE=bfloat16):
            # ~27% less hot-loop HBM traffic; see with_value_dtype. Tables
            # build in f32 first, then storage casts.
            return self.with_fast_path().with_value_dtype(vd)
        return self.with_fast_path()

    def with_value_dtype(self, dtype) -> "SparseFeatures":
        """Store feature VALUES in a narrower dtype (e.g. ``jnp.bfloat16``).

        The fused GLM pass is HBM-bound and values are 8 B of its 15 B
        per-entry stream (with int16 digit splits; 19 B at int32), so
        bfloat16 storage cuts hot-loop traffic ~27% on TPU; the ops upcast
        on load and accumulate in the operand precision, so only storage
        narrows. One-hot / binary / small-integer features are EXACT in
        bfloat16; continuous features round to 8 mantissa bits — opting in
        accepts that quantization. The Pallas tables are f32-only and are
        dropped; the XLA fast path's column table is re-cast to match.
        """
        dt = jnp.dtype(dtype)
        if jnp.dtype(self.val.dtype) == dt:
            return self
        out = dataclasses.replace(self, val=self.val.astype(dt))
        if out.fast is not None:
            out = dataclasses.replace(
                out,
                fast=dataclasses.replace(
                    out.fast, cs_val=out.fast.cs_val.astype(dt)
                ),
            )
        if out.pallas is not None and dt != jnp.float32:
            out = dataclasses.replace(out, pallas=None)
        return out

    def without_fast_path(self) -> "SparseFeatures":
        """Drop the fast/pallas layouts (e.g. before row-sharding: the
        column-sorted tables are not partitionable along the row axis)."""
        if self.fast is None and self.pallas is None:
            return self
        return dataclasses.replace(self, fast=None, pallas=None)

    def _pallas_mode(self, dtype) -> Optional[bool]:
        """None = don't use the kernels; else the ``interpret`` flag."""
        import os

        if self.pallas is None:
            return None
        if jnp.dtype(dtype) != jnp.float32:
            # The slot-table kernels are f32-only; --dtype float64 runs must
            # not silently think they are on the Pallas path (VERDICT r3
            # weak #5) — say so once, then use the XLA fast path.
            global _WARNED_PALLAS_F64
            if not _WARNED_PALLAS_F64:
                _WARNED_PALLAS_F64 = True
                import logging

                # warning, not info: without a configured handler INFO is
                # dropped and the downgrade would stay silent for direct
                # estimator-API users.
                logging.getLogger("photon_tpu.ops").warning(
                    "Pallas tables attached but operand dtype is %s; the "
                    "kernels are float32-only — using the XLA fast path",
                    jnp.dtype(dtype),
                )
            return None
        if jax.default_backend() in REAL_ACCELERATOR_BACKENDS:
            return False  # on the chip the kernels compile or fail loudly
        return (True if os.environ.get("PHOTON_PALLAS_INTERPRET") == "1"
                else None)

    def _use_pallas(self, dtype) -> bool:
        return self._pallas_mode(dtype) is not None

    def _formulation(self, op: str, dtype) -> tuple:
        """``(kind, interpret)``: which formulation ``op`` puts into the
        program being traced here — "pallas" (with its interpret flag),
        "fast" or "plain". Counted once per trace (or eager call) in
        ``sparse_op_traces_total{op, formulation}``, so a run can say what
        its programs really hold, not what a look-alike batch would get."""
        pass_counter.record(op)
        interp = self._pallas_mode(dtype)
        kind = ("pallas" if interp is not None
                else "fast" if self.fast is not None else "plain")
        REGISTRY.counter(
            "sparse_op_traces_total",
            "sparse feature ops traced into programs, by formulation",
        ).inc(op=op, formulation=kind)
        return kind, interp

    @jax.named_scope("sparse.matvec")
    def matvec(self, w: Array) -> Array:
        kind, interp = self._formulation("matvec", w.dtype)
        if kind == "pallas":
            from photon_tpu.ops.pallas_sparse import matvec_pallas

            return matvec_pallas(self.pallas, w, interpret=interp)
        if kind == "fast":
            from photon_tpu.ops.fast_sparse import matvec_fast

            return matvec_fast(self.fast, self.val, w, self.dim)
        # Gather through an extended vector with a zero ghost column so
        # padding indices read 0 — no masking needed in the hot loop.
        w_ext = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        return jnp.sum(w_ext[self.idx] * self.val, axis=-1)

    @jax.named_scope("sparse.rmatvec")
    def rmatvec(self, v: Array) -> Array:
        kind, interp = self._formulation("rmatvec", v.dtype)
        if kind == "pallas":
            from photon_tpu.ops.pallas_sparse import rmatvec_pallas

            return rmatvec_pallas(self.pallas, v, interpret=interp)
        if kind == "fast":
            from photon_tpu.ops.fast_sparse import rmatvec_fast

            return rmatvec_fast(self.fast, v, self.dim)
        contrib = (v[:, None] * self.val).ravel()
        out = jax.ops.segment_sum(
            contrib, self.idx.ravel(), num_segments=self.dim + 1
        )
        return out[: self.dim]

    @jax.named_scope("sparse.sq_rmatvec")
    def sq_rmatvec(self, v: Array) -> Array:
        kind, interp = self._formulation("sq_rmatvec", v.dtype)
        if kind == "pallas":
            from photon_tpu.ops.pallas_sparse import rmatvec_pallas

            return rmatvec_pallas(self.pallas, v, square_vals=True,
                                  interpret=interp)
        if kind == "fast":
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
        """Sparse features gain the MXU layouts (see
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
            else:
                span.set(formulation="pallas" if attached.pallas
                         is not None else "fast")
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
