"""What the algorithm needs: bytes and floating-point operations of a fit,
counted from shapes and the solver's own pass counts.

Counted from the algorithm, not from any formulation of it, so the same fit
reads the same work whichever sparse path implements it. The sparse pass's
arithmetic is ``bench.py``'s (12 B an entry): one data pass (a ``matvec``
X.w or an ``rmatvec`` X^T.r) has to read each stored entry's column id
(int32) and value (float32) and touch one float32 of the vector it gathers
from or adds into, and does one multiply and one add per entry.
"""
from __future__ import annotations

BYTES_PER_ENTRY = 12          # int32 id + float32 value + float32 gathered
FLOPS_PER_ENTRY = 2           # multiply, add
VECTOR_BYTES = 4              # float32


def pass_bytes(rows: int, nnz_per_row: int, dim: int) -> int:
    """Bytes one data pass over ``rows x nnz_per_row`` entries has to move:
    the entries, the per-row vector (read or written once) and the
    per-feature vector (read or written once)."""
    return (rows * nnz_per_row * BYTES_PER_ENTRY
            + (rows + dim) * VECTOR_BYTES)


def pass_flops(rows: int, nnz_per_row: int) -> int:
    return rows * nnz_per_row * FLOPS_PER_ENTRY


def fixed_work(rows: int, nnz_per_row: int, dim: int, data_passes: int) -> dict:
    """A fixed-effect solve that made ``data_passes`` passes."""
    return {"bytes": data_passes * pass_bytes(rows, nnz_per_row, dim),
            "flops": data_passes * pass_flops(rows, nnz_per_row)}


def random_effect_work(rows_per_entity: int, nnz_per_row: int, dim: int,
                       entity_passes: int) -> dict:
    """Per-entity solves that made ``entity_passes`` passes in all, each over
    one entity's ``rows_per_entity x nnz_per_row`` entries."""
    return fixed_work(rows_per_entity, nnz_per_row, dim, entity_passes)


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work`` and which of its two
    peaks binds: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s."""
    by_flops = work["flops"] / peak["flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")


def add(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in ("bytes", "flops")}
