"""Device-mesh construction and sharding helpers.

This is the rebuild's replacement for the reference's Spark runtime substrate
(SURVEY.md §1 layer R / §5.8): instead of executors + treeAggregate +
TorrentBroadcast, a `jax.sharding.Mesh` with named axes and XLA collectives
over ICI/DCN.

Axis conventions (SURVEY.md §2.6):
  * ``data``    — batch rows (P1 data parallelism; gradient psum),
  * ``entity``  — random-effect entities (P2/P6 expert-style sharding),
  * ``feature`` — coefficient dimension (P3 sharded optimizer state).

A mesh may use any subset; a multi-slice deployment adds an outer DCN axis by
listing it first (slowest-varying) so collectives ride ICI within a slice.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401 - re-exported to parallel/*
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
ENTITY_AXIS = "entity"
FEATURE_AXIS = "feature"
DCN_AXIS = "dcn"

# An axis argument throughout parallel/ may be one mesh axis name or a tuple
# of names (e.g. ("dcn", "data") — rows sharded over slices x chips, with
# psum lowering hierarchically: ICI within a slice, DCN across slices).
AxisSpec = "str | tuple[str, ...]"


def axis_tuple(axis) -> tuple:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axes_size(mesh: Mesh, axis) -> int:
    return int(np.prod([mesh.shape[a] for a in axis_tuple(axis)]))


def make_mesh(
    axis_sizes: dict[str, int] | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh from {axis: size}. Default: all devices on ``data``."""
    devices = list(devices if devices is not None else jax.devices())
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = tuple(axis_sizes)
    sizes = tuple(axis_sizes.values())
    n = int(np.prod(sizes))
    if n != len(devices):
        raise ValueError(
            f"mesh wants {n} devices ({axis_sizes}) but {len(devices)} available"
        )
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def make_multislice_mesh(
    n_slices: int,
    axis_sizes: dict[str, int] | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_axis: str = DCN_AXIS,
) -> Mesh:
    """2-level mesh: an outer ``dcn`` axis over slices (slowest-varying) and
    the given ICI axes within each slice — the multi-slice deployment shape
    (SURVEY.md §5.8: hierarchical psum replaces treeAggregate; ICI within a
    slice, DCN across).

    On real multi-slice TPU topologies the device order comes from
    ``mesh_utils.create_hybrid_device_mesh`` so that the outer axis truly
    crosses slice boundaries (minimizing DCN traffic for inner-axis
    collectives); on single-slice or host-simulated devices it falls back to
    a plain reshape, which exercises identical program structure.
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) % n_slices:
        raise ValueError(f"{len(devices)} devices not divisible by {n_slices} slices")
    per_slice = len(devices) // n_slices
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: per_slice}
    inner = tuple(axis_sizes.values())
    if int(np.prod(inner)) != per_slice:
        raise ValueError(
            f"inner axes {axis_sizes} want {int(np.prod(inner))} devices/slice, "
            f"have {per_slice}"
        )
    names = (dcn_axis,) + tuple(axis_sizes)
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    if len(slice_ids) > 1 and len(slice_ids) != n_slices:
        # On real multi-slice hardware a mismatched dcn size would silently
        # put inner-axis collectives on DCN links — exactly the pathology a
        # 2-level mesh exists to prevent. Refuse instead.
        raise ValueError(
            f"devices span {len(slice_ids)} slices but n_slices={n_slices}; "
            "the dcn axis must match the physical slice count"
        )
    if n_slices > 1 and len(slice_ids) == n_slices:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1,) + inner,
            dcn_mesh_shape=(n_slices,) + (1,) * len(inner),
            devices=devices,
        )
    else:
        dev_array = np.asarray(devices).reshape((n_slices,) + inner)
    return Mesh(dev_array, names)


def batch_sharding(mesh: Mesh, axis=DATA_AXIS) -> NamedSharding:
    """Shard the leading (row) dimension over ``axis``; replicate the rest
    (PartitionSpec leaves unmentioned trailing dims unsharded, for any rank).

    The one spec used by every batch-distribution path (device_put here,
    ``make_array_from_process_local_data`` in parallel/distributed.py), so
    shardings from either compare equal."""
    return NamedSharding(mesh, P(axis_tuple(axis)))

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def note_sharded_bytes(kind: str, tree) -> None:
    """Record, per device, the bytes it holds of a freshly placed sharded
    pytree (``sharded_bytes_per_device{array=kind, device=id}``) — how a
    run shows its rows or entities are spread over the mesh and not all on
    device 0. Shard metadata only: no transfer, no sync."""
    from photon_tpu.obs.metrics import REGISTRY

    per_device: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes)
    gauge = REGISTRY.gauge(
        "sharded_bytes_per_device",
        "bytes each device holds of the last placed sharded arrays, by kind")
    for device, nbytes in per_device.items():
        gauge.set(nbytes, array=kind, device=str(device))


def shard_batch_pytree(batch, mesh: Mesh, axis=DATA_AXIS):
    """Device-put every array leaf of a batch pytree row-sharded over ``axis``
    (one name or a tuple, e.g. ``("dcn", "data")``).

    All leaves of a LabeledBatch share the same leading row count, so one
    spec applies uniformly (ELL idx/val are [N, K]; labels/offsets/weights
    are [N]).
    """
    sharding = batch_sharding(mesh, axis)
    return jax.tree.map(lambda leaf: jax.device_put(leaf, sharding), batch)


def strip_unshardable_aux(batch_or_features):
    """Drop the ``fast`` tables before row distribution — their
    column-sorted layout is NOT partitionable along the row axis and
    sharding it would corrupt results. Accepts a LabeledBatch or a bare
    features container; the one definition every distribution path uses."""
    import dataclasses

    from photon_tpu.data.batch import SparseFeatures

    obj = batch_or_features
    feats = getattr(obj, "features", obj)
    if not isinstance(feats, SparseFeatures) or feats.fast is None:
        return obj
    bare = feats.without_fast_path()
    return bare if feats is obj else dataclasses.replace(obj, features=bare)


def pad_and_shard_batch(batch, mesh: Mesh, axis=DATA_AXIS):
    """The canonical row-distribution preamble: strip the non-row-shardable
    aux tables (``strip_unshardable_aux``), pad rows to the axis-size
    multiple (weight-0 / zero-feature padding), and device_put row-sharded.
    Accepts a LabeledBatch or a bare features container — shared by
    training (``fit_data_parallel``) and scoring (``GameTransformer``)."""
    axis_size = axes_size(mesh, axis)
    batch = strip_unshardable_aux(batch)
    if batch.n_rows % axis_size:
        batch = pad_rows_to_multiple(batch, axis_size)
    return shard_batch_pytree(batch, mesh, axis)


def pad_rows_to_multiple(arrs_n_leading, multiple: int):
    """Host-side: pad row count to a multiple (for even sharding), returning
    the padded pytree. Padding is zero-fill — for a LabeledBatch the padded
    rows carry weight 0 and are invisible to objectives/evaluators, no
    further masking required — except ELL sparse index arrays, whose padded
    rows point at the ghost column ``dim`` to keep the SparseFeatures
    sentinel invariant ("id == D marks padding")."""
    import numpy as _np

    def pad(a, fill=0):
        n = a.shape[0]
        r = (-n) % multiple
        if r == 0:
            return a
        pad_width = [(0, r)] + [(0, 0)] * (a.ndim - 1)
        return _np.pad(_np.asarray(a), pad_width, constant_values=fill)

    from photon_tpu.data.batch import (
        DenseFeatures,
        LabeledBatch,
        SparseFeatures,
    )

    # Bare feature containers: arrays ALREADY on device pad device-side
    # (no host round-trip of [N, K] arrays to append a few zero rows);
    # host-numpy arrays pad host-side so the subsequent
    # device_put(NamedSharding) still streams shards directly to their
    # devices without ever materializing the whole array on one.
    def _pad2(a, fill):
        r = (-a.shape[0]) % multiple
        if isinstance(a, jax.Array):
            ext = (jax.numpy.full((r, a.shape[1]), fill, a.dtype)
                   if fill else jax.numpy.zeros((r, a.shape[1]), a.dtype))
            return jax.numpy.concatenate([a, ext])
        return pad(a, fill)

    if isinstance(arrs_n_leading, SparseFeatures):
        sf = arrs_n_leading
        if (-sf.n_rows) % multiple == 0:
            return sf
        return SparseFeatures(
            idx=_pad2(sf.idx, sf.dim), val=_pad2(sf.val, 0), dim=sf.dim
        )
    if isinstance(arrs_n_leading, DenseFeatures):
        if (-arrs_n_leading.x.shape[0]) % multiple == 0:
            return arrs_n_leading
        return DenseFeatures(_pad2(arrs_n_leading.x, 0))

    if isinstance(arrs_n_leading, LabeledBatch) and isinstance(
        arrs_n_leading.features, SparseFeatures
    ):
        # Stays HOST numpy on purpose: the caller's device_put(NamedSharding)
        # then streams shards directly to their devices; wrapping in
        # jnp.asarray here would first materialize the whole padded batch on
        # the default device.
        batch = arrs_n_leading
        sf = batch.features
        return LabeledBatch(
            features=SparseFeatures(
                idx=pad(sf.idx, fill=sf.dim),
                val=pad(sf.val),
                dim=sf.dim,
            ),
            labels=pad(batch.labels),
            offsets=pad(batch.offsets),
            weights=pad(batch.weights),
        )
    return jax.tree.map(pad, arrs_n_leading)
