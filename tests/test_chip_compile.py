"""Ask the TPU's compiler, without a TPU, about every kernel chip_smoke.py's
main path dispatches — at the smoke's real shapes, for a described v5e.

The compiler is installed here and compiles for a chip that is described,
not attached (guide on-chip-measurement §2, rehearsal 3). Nothing runs, so
this says nothing about results or times; it says whether the chip would
accept the program, which interpret-mode tests cannot. One file on purpose:
the process that describes the topology holds the TPU library, and a second
file could land on another xdist worker. The topology is described inside a
fixture — never at import — so every worker collects the same tests.

Shapes (chip_smoke.py REAL): fixed effect 2^17 rows x 32 nnz x 2^18
features; random effect 8,192 users x 16 rows x 5 nnz in a 16-wide local
subspace; serving micro-batch 64 rows x 128 nnz.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.ops import fast_sparse
from photon_tpu.ops.fast_sparse import (
    CHUNK,
    ROW_PAD,
    WINDOW_BLOCKS,
    FastSparseAux,
    PlaneTable,
    ROWS_PER_STEP,
    RowSliceXtr,
    RowSliceXw,
    WindowTable,
)

N, K, D = 1 << 17, 32, 1 << 18          # fixed effect
E, S, KU, PU = 8192, 16, 5, 16          # random-effect bucket
CS_ROWS, Q = 3072, 2048                 # fast-path column table at that data
SERVE_B, SERVE_K = 64, 128              # serving_driver defaults


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these out of it. And
    # compile what the drivers compile: conftest turns x64 on for the
    # golden tests, the drivers' float32 default leaves it off (with it on,
    # the fast-path rmatvec at this shape asks the chip for 25.7 GB).
    was_on = jax.config.jax_enable_compilation_cache
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    jax.config.update("jax_enable_x64", x64)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    return mesh, NamedSharding(mesh, P("data")), NamedSharding(mesh, P())


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _problem(spec):
    """The problem the smoke's ``--coordinate`` flag makes, as the jit key
    ``GLMOptimizationProblem.fit`` builds from it."""
    from photon_tpu.cli.params import parse_coordinate_spec
    from photon_tpu.types import TaskType

    c = parse_coordinate_spec(spec)
    return c.optimization.with_reg_weight(c.reg_weights[0]).problem(
        TaskType.LOGISTIC_REGRESSION)


FIXED = "fixed:type=fixed,shard=global,reg=L2,reg_weights=1"
FIXED_TRON = FIXED + ",optimizer=TRON"
PER_USER = "perUser:type=random,re_type=userId,shard=user,reg=L2,reg_weights=1"


def _fixed_features(sh, fast: bool, n=N, k=K, d=D,
                    cs_rows=CS_ROWS) -> SparseFeatures:
    """The smoke's fixed effect; with ``fast`` the row-slice tables, which
    is what ``build_fast_aux`` keeps at its uniform random columns."""
    digits = (-(-n // ROW_PAD) * ROW_PAD * k,)   # flat, whole row blocks
    aux = FastSparseAux(
        xw=RowSliceXw(hi=_sds(digits, "int16", sh),
                      lo=_sds(digits, "int8", sh)),
        xtr=RowSliceXtr(
            cs_rhi=_sds((cs_rows, Q), "int16", sh),
            cs_rlo=_sds((cs_rows, Q), "int8", sh),
            cs_clo=_sds((cs_rows, Q), "int8", sh),
            cs_val=_sds((cs_rows, Q), "float32", sh),
            cs_range=_sds((cs_rows,), "int32", sh),
            n_ranges=-(-d // 128), n_row_blocks=-(-n // 128)),
    ) if fast else None
    return SparseFeatures(
        idx=_sds((n, k), "int32", sh), val=_sds((n, k), "float32", sh),
        dim=d, fast=aux)


def _window_table(sh, rows: int, q: int, n_red: int, n_gat: int):
    return WindowTable(
        word=_sds((rows, q), "int32", sh), val=_sds((rows, q), "float32", sh),
        passes=_sds((rows * q // CHUNK,), "int32", sh),
        range=_sds((rows,), "int32", sh), n_ranges=-(-n_red // 128),
        n_windows=-(-n_gat // (WINDOW_BLOCKS * 128)))


# The ``window`` tables as ``build_fast_aux`` shapes them from the cells'
# data (and, forced, from the smoke's): rows, nnz, features, then the
# [table rows, Q] of the X.w and of the X^T.r table.
WINDOW_SHAPES = {
    "glm_fit": (65536, 76, 47237, (2560, 2048), (2696, 2048)),
    "glm_fit_tron": (72309, 52, 20959, (2264, 2048), (1904, 2048)),
    "game_fit": (1002640, 8, 3765, (7840, 1024), (3936, 2048)),
    "smoke": (N, K, D, (2048, 2048), (3072, 2048)),
}


def _plane_table(sh, n: int, k: int, d: int) -> PlaneTable:
    rows = -(-n // (ROWS_PER_STEP * CHUNK)) * ROWS_PER_STEP * k
    return PlaneTable(
        word=_sds((rows, CHUNK), "int32", sh),
        val=_sds((rows, CHUNK), "float32", sh),
        passes=_sds((rows,), "int32", sh), n_planes=k,
        n_windows=-(-d // (WINDOW_BLOCKS * 128)))


def _window_features(sh, shape: str, planes: bool = False,
                     xtr_planes: bool = False) -> SparseFeatures:
    """The cell's features on the kernel's tables; with ``planes`` X.w's
    is the ``planes`` table, with ``xtr_planes`` X^T.r's too, the one
    table both ops share (what the build chooses in ``game_fit``, and
    forced in the others)."""
    n, k, d, xw, xtr = WINDOW_SHAPES[shape]
    table = _plane_table(sh, n, k, d)
    return SparseFeatures(
        idx=_sds((n, k), "int32", sh), val=_sds((n, k), "float32", sh), dim=d,
        fast=FastSparseAux(
            xw=table if planes else _window_table(sh, *xw, n, d),
            xtr=table if xtr_planes else _window_table(sh, *xtr, d, n)))


@pytest.fixture
def compiled_kernel(monkeypatch):
    """The kernel itself and not its interpreter, though the backend here is
    a CPU (steered in the test, not by an option of the program)."""
    monkeypatch.setattr(fast_sparse, "_interpret", lambda: False)


def _holds_no_row_slices(compiled, n: int, k: int) -> None:
    """The kernel is in the program, and no float32 ``[*, 128]`` value of
    the entries' length (what the row-slice gather writes) is."""
    text = compiled.as_text()
    assert ("sparse_gather_reduce" in text or "sparse_plane_lookup" in text
            or "sparse_plane_scatter" in text or "tpu_custom_call" in text)
    wide = [int(m) for m in re.findall(r"f32\[(\d+),128\]", text)]
    assert all(rows < n * k // 8 for rows in wide), max(wide)


def _fixed_batch(sh, fast: bool) -> LabeledBatch:
    return LabeledBatch(
        features=_fixed_features(sh, fast),
        labels=_sds((N,), "float32", sh), offsets=_sds((N,), "float32", sh),
        weights=_sds((N,), "float32", sh))


def _bucket(sh, entities: int):
    """(batches, w0, local_mask) of one random-effect bucket."""
    def a(*shape, dtype="float32"):
        return _sds((entities,) + shape, dtype, sh)

    batches = LabeledBatch(
        features=SparseFeatures(idx=a(S, KU, dtype="int32"), val=a(S, KU),
                                dim=PU),
        labels=a(S), offsets=a(S), weights=a(S))
    return batches, a(PU), a(PU)


# ------------------------------------------------------ one chip, main path


@pytest.mark.parametrize("op,vec_len", [
    ("matvec", D), ("rmatvec", N), ("sq_rmatvec", N)])
def test_default_sparse_path_compiles(one_chip, op, vec_len):
    """The layouts ``with_accelerator_paths`` attaches on a TPU (``fast``)
    lower at 2^17 x 2^18 x 32."""
    feats = _fixed_features(one_chip, fast=True)
    vec = _sds((vec_len,), "float32", one_chip)
    jax.jit(lambda f, x: getattr(f, op)(x)).lower(feats, vec).compile()


@pytest.mark.parametrize("op", ["matvec", "sq_rmatvec"])
@pytest.mark.parametrize("shape", list(WINDOW_SHAPES))
def test_window_ops_compile_and_write_no_row_slices(
        one_chip, compiled_kernel, shape, op):
    """``gather_reduce`` over either table at the three cells' shapes and
    the smoke's: the chip's compiler takes it, and the program's
    temporaries are the ``[table rows, 128]`` partials, not 512 B an entry
    (2.87, 2.05 and 4.65 GB in the cells' fit programs before)."""
    feats = _window_features(one_chip, shape)
    n, k, d = WINDOW_SHAPES[shape][:3]
    vec = _sds((d if op == "matvec" else n,), "float32", one_chip)
    compiled = jax.jit(lambda f, x: getattr(f, op)(x)).lower(
        feats, vec).compile()
    _holds_no_row_slices(compiled, n, k)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("op", ["matvec", "rmatvec", "sq_rmatvec"])
@pytest.mark.parametrize("shape", list(WINDOW_SHAPES))
def test_x_w_by_planes_compiles_and_writes_no_row_slices(
        one_chip, compiled_kernel, shape, op):
    """``plane_lookup`` and ``plane_scatter`` at the cells' shapes and the
    smoke's (8, 32, 52 and 76 planes a chunk; up to 49 windows of the
    gradient resident): the chip's compiler takes them, and the program
    holds no temporary of the entries' length."""
    feats = _window_features(one_chip, shape, planes=True, xtr_planes=True)
    n, k, d = WINDOW_SHAPES[shape][:3]
    compiled = jax.jit(lambda f, x: getattr(f, op)(x)).lower(
        feats, _sds((d if op == "matvec" else n,), "float32", one_chip)
    ).compile()
    assert ("sparse_plane_lookup" if op == "matvec"
            else "sparse_plane_scatter") in compiled.as_text()
    _holds_no_row_slices(compiled, n, k)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("shape,spec,planes", [
    ("glm_fit", FIXED, False), ("game_fit", FIXED, False),
    ("smoke", FIXED, False), ("glm_fit_tron", FIXED_TRON, False),
    ("game_fit", FIXED, True), ("glm_fit_tron", FIXED_TRON, True)])
def test_fit_program_holds_the_kernel_and_no_row_slices(
        one_chip, compiled_kernel, shape, spec, planes):
    """``_fit_jitted`` (L-BFGS, and TRON with its nested loops) over the
    ``window`` tables, and with X.w by ``planes``: the kernel inside
    ``lax.while_loop``s compiles for the chip, and the fit program's
    temporaries fall to a few vectors."""
    from photon_tpu.functions.problem import _fit_jitted

    feats = _window_features(one_chip, shape, planes)
    n, k, d = WINDOW_SHAPES[shape][:3]
    batch = LabeledBatch(
        features=feats, labels=_sds((n,), "float32", one_chip),
        offsets=_sds((n,), "float32", one_chip),
        weights=_sds((n,), "float32", one_chip))
    vec = _sds((d,), "float32", one_chip)
    compiled = _fit_jitted.lower(
        _problem(spec), batch, vec, vec, None, None,
        _sds((), "float32", one_chip)).compile()
    _holds_no_row_slices(compiled, n, k)
    # Read here: 19 MB (glm_fit), 31 MB (game_fit), 37 MB (smoke), 1 MB
    # (glm_fit_tron).
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_a_fit_over_ten_million_rows_holds_no_whole_table_of_row_slices(
        one_chip, compiled_kernel):
    """``game_fit_ragged``'s fixed effect (PR 33): 9,997,911 rows x 4 over
    26,765 columns. X.w goes by ``planes`` (PR 37; five windows of ``w``);
    X^T.r keeps the row-slice table (a column range's slots read rows all
    over a vector too long for VMEM), whose 40 M slots would be 20 GB of
    row slices at once. Walked a block of table rows at a time the fit
    program read 1.6 GB of temporaries here, and compiles."""
    from photon_tpu.functions.problem import _fit_jitted

    n, k, d, cs_rows = 9997911, 4, 26765, 19932
    sh = one_chip
    xtr = RowSliceXtr(
        cs_rhi=_sds((cs_rows, Q), "int32", sh),
        cs_rlo=_sds((cs_rows, Q), "int8", sh),
        cs_clo=_sds((cs_rows, Q), "int8", sh),
        cs_val=_sds((cs_rows, Q), "float32", sh),
        cs_range=_sds((cs_rows,), "int32", sh),
        n_ranges=-(-d // 128), n_row_blocks=-(-n // 128))
    feats = SparseFeatures(
        idx=_sds((n, k), "int32", sh), val=_sds((n, k), "float32", sh), dim=d,
        fast=FastSparseAux(xw=_plane_table(sh, n, k, d), xtr=xtr))
    batch = LabeledBatch(
        features=feats, labels=_sds((n,), "float32", sh),
        offsets=_sds((n,), "float32", sh), weights=_sds((n,), "float32", sh))
    vec = _sds((d,), "float32", sh)
    compiled = _fit_jitted.lower(
        _problem(FIXED), batch, vec, vec, None, None,
        _sds((), "float32", sh)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_a_fit_over_ten_million_rows_scatters_through_its_planes(
        one_chip, compiled_kernel):
    """``game_fit_ragged``'s fixed effect since PR 39: 9,997,911 rows x 4
    over 26,765 columns, both ops on the one ``PlaneTable`` (X^T.r scatters
    into a gradient of five windows resident in VMEM, ``r`` streamed past).
    The fit program compiles for the chip, holds the scatter, and its
    temporaries are the vectors of its rows, far under the 1.6 GB of row
    slices the case above holds (read here: 0.33 GB against 1.58, PR 39)."""
    from photon_tpu.functions.problem import _fit_jitted

    n, k, d = 9997911, 4, 26765
    sh = one_chip
    table = _plane_table(sh, n, k, d)
    feats = SparseFeatures(
        idx=_sds((n, k), "int32", sh), val=_sds((n, k), "float32", sh), dim=d,
        fast=FastSparseAux(xw=table, xtr=table))
    batch = LabeledBatch(
        features=feats, labels=_sds((n,), "float32", sh),
        offsets=_sds((n,), "float32", sh), weights=_sds((n,), "float32", sh))
    vec = _sds((d,), "float32", sh)
    compiled = _fit_jitted.lower(
        _problem(FIXED), batch, vec, vec, None, None,
        _sds((), "float32", sh)).compile()
    text = compiled.as_text()
    assert "sparse_plane_scatter" in text and "sparse_plane_lookup" in text
    _holds_no_row_slices(compiled, n, k)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("n,k,d,cs_rows,temp_gb", [
    (65536, 76, 47237, 2696, 3.0),    # glm_fit: rcv1.binary's row width
    (72309, 52, 20959, 1904, 3.0),    # glm_fit_tron: real-sim whole, odd rows
    (1002640, 8, 3765, 3936, 4.5),    # game_fit: rows off every tile grid
])
def test_matvec_reads_row_slices_as_gathered(one_chip, n, k, d, cs_rows,
                                             temp_gb):
    """``fast`` X.w at the benchmark's shapes (what an op keeps over the
    break-even): the gathered ``[rows*nnz, 128]`` row
    slices reach the lane select with no ``[rows, nnz, 128]`` relayout
    between (a physical copy where nnz is no multiple of 8: 5.23 GB of
    temporaries at the first shape before), and the flat result's reshape
    compiles in seconds at a row count off the 1024 grid."""
    feats = _fixed_features(one_chip, True, n, k, d, cs_rows)
    compiled = jax.jit(lambda f, x: f.matvec(x)).lower(
        feats, _sds((d,), "float32", one_chip)).compile()
    assert not re.search(rf"f32\[\d+,{k},128\]\S* (reshape|copy)\(",
                         compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9


def test_glm_fit_compiles(one_chip):
    """One whole fixed-effect L-BFGS program over the smoke's batch, on the
    row-slice tables its data keeps."""
    from photon_tpu.functions.problem import _fit_jitted

    vec = _sds((D,), "float32", one_chip)
    compiled = _fit_jitted.lower(
        _problem(FIXED), _fixed_batch(one_chip, fast=True), vec, vec, None,
        None, _sds((), "float32", one_chip)).compile()
    # 3.35 GB: the X^T.r gather's [3072, 2048, 128] row slices (3.2 GB);
    # X.w's flat [2^22, 128] slices (2.15 GB) are not live beside them, and
    # no relayout of either exists at any row width.
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


# The solver the static router picks at the smoke's bucket (primal Newton)
# compiles at the whole bucket; the other two tiers at one device's share of
# it under the four-chip mesh. Compile time grows steeply with the entity
# count (about 2 s at 256 entities, 6 s at 2,048, 60 s at 8,192 — ROADMAP
# S6), so one full-size case is what the suite can afford.
@pytest.mark.parametrize("solver,entities", [
    ("fit_bucket_newton", E),
    ("fit_bucket_newton_dual", E // 4),
    ("fit_bucket_vmapped", E // 4),
])
def test_random_effect_bucket_solver_compiles(one_chip, solver, entities):
    from photon_tpu.game.newton_re import (
        fit_bucket_newton,
        fit_bucket_newton_dual,
    )
    from photon_tpu.game.random_effect import _fit_bucket_jitted

    problem = _problem(PER_USER)
    batches, w0, mask = _bucket(one_chip, entities)
    if solver == "fit_bucket_newton":
        lowered = fit_bucket_newton.lower(problem, batches, w0, mask, None)
    elif solver == "fit_bucket_newton_dual":
        lowered = fit_bucket_newton_dual.lower(
            problem, batches, w0, mask, None, 1)  # u_max 1: the intercept
    else:
        lowered = _fit_bucket_jitted.lower(
            problem, batches, w0, mask, None, None)
    lowered.compile()


def test_a_ragged_size_class_compiles_in_seconds(one_chip):
    """``game_fit_ragged``'s size class of 12,874 users x 256 padded rows
    (3 entries a slot, 32 local columns): its Newton program and its
    scorer. Both pick by compare-select since PR 33. At this shape, for
    a described v5e, the design's vmapped scatter-add alone compiled for
    15.5 s and the scorer's batched gather for 309 s, a program a size
    class (the Newton program 3.9 s and the scorer 1.9 s now, all read
    here, PR 33); the bound of 60 s tells the two apart on any machine.
    Since PR 34 the Newton program factorizes its [E,32,32] systems with
    the entities on the minor axis (``_lane_cholesky_solve``): no
    ``Cholesky`` custom call is left in it, and it compiles in 2.4 s
    where the library form took 2.2 s (as a process's second program; 4.1
    and 2.5 s as its first; 1.07 GB of temporaries either way; read here
    for a described v5e, PR 34)."""
    import time

    from photon_tpu.data.random_effect import _bucket_scores
    from photon_tpu.game.newton_re import fit_bucket_newton

    e, s, k, p = 12874, 256, 3, 32
    sh = one_chip

    def a(*shape, dtype="float32"):
        return _sds((e,) + shape, dtype, sh)

    batches = LabeledBatch(
        features=SparseFeatures(idx=a(s, k, dtype="int32"), val=a(s, k), dim=p),
        labels=a(s), offsets=a(s), weights=a(s))
    t0 = time.perf_counter()
    solver = fit_bucket_newton.lower(
        _problem(PER_USER), batches, a(p), a(p), None).compile()
    scorer = _bucket_scores.lower(
        a(s, k, dtype="int32"), a(s, k), a(p)).compile()
    assert time.perf_counter() - t0 < 60.0
    # Fused: the scorer holds nothing of the [E,S,K,P] pick, and the solver
    # the design and its weighted copy (1.52 GB with the scatter).
    assert scorer.memory_analysis().temp_size_in_bytes < 1e6
    assert solver.memory_analysis().temp_size_in_bytes < 1.3e9
    assert "Cholesky" not in solver.as_text()


def test_the_widest_newton_program_holds_no_library_factorization(one_chip):
    """256 entities x 64 rows in 128 local columns (the widest primal
    bucket measured routing admits, ``NEWTON_CHUNK_MAX_P``, at the chunk
    ladder's smallest size): the lane form at every width, so no
    ``Cholesky`` custom call here either (on the chip 1.82 ms for the
    call's 7.03 at this shape, PERF.md §6, PR 34)."""
    from photon_tpu.game.newton_re import NEWTON_CHUNK_MAX_P, fit_bucket_newton

    e, s, k, p = 256, 64, 3, NEWTON_CHUNK_MAX_P

    def a(*shape, dtype="float32"):
        return _sds((e,) + shape, dtype, one_chip)

    batches = LabeledBatch(
        features=SparseFeatures(idx=a(s, k, dtype="int32"), val=a(s, k), dim=p),
        labels=a(s), offsets=a(s), weights=a(s))
    solver = fit_bucket_newton.lower(
        _problem(PER_USER), batches, a(p), a(p), None).compile()
    assert "Cholesky" not in solver.as_text()


def test_a_wide_local_dimension_is_not_picked_by_compare_select(one_chip):
    """4,096 entities x 64 rows x 32 entries in 1,024 local columns (the
    L-BFGS and dual routes' widths): over ``SELECT_MAX_COLUMNS`` the
    scorer gathers and the design scatters, an entry's work not growing
    with the columns. The compare-select pick there is 1,024 compares an
    entry, 34 GB if its [E,S,K,P] pick were ever held (the CPU's compiler
    holds it). Compiled here in seconds; the temporaries are the inputs'
    relayout and the design."""
    import time

    from photon_tpu.data.random_effect import (
        SELECT_MAX_COLUMNS,
        _bucket_scores,
    )
    from photon_tpu.game.newton_re import _dense_design

    e, s, k, p = 4096, 64, 32, 1024
    assert p > SELECT_MAX_COLUMNS
    sh = one_chip

    def a(*shape, dtype="float32"):
        return _sds((e,) + shape, dtype, sh)

    batches = LabeledBatch(
        features=SparseFeatures(idx=a(s, k, dtype="int32"), val=a(s, k), dim=p),
        labels=a(s), offsets=a(s), weights=a(s))
    t0 = time.perf_counter()
    scorer = _bucket_scores.lower(
        a(s, k, dtype="int32"), a(s, k), a(p)).compile()
    design = jax.jit(lambda b: _dense_design(b, jnp.float32)).lower(
        batches).compile()
    assert time.perf_counter() - t0 < 60.0
    assert scorer.memory_analysis().temp_size_in_bytes < 1e9
    assert design.memory_analysis().temp_size_in_bytes < 3e9


def test_additive_score_rows_compiles(one_chip):
    """The serving kernel at the server's largest warmed micro-batch."""
    from photon_tpu.estimators.game_transformer import additive_score_rows

    def rows(k, dtype):
        return _sds((SERVE_B, k), dtype, one_chip)

    additive_score_rows.lower(
        _sds((SERVE_B,), "float32", one_chip),
        {"global": rows(SERVE_K, "int32"), "user": rows(SERVE_K, "int32")},
        {"global": rows(SERVE_K, "float32"),
         "user": rows(SERVE_K, "float32")},
        {"fixed": _sds((D + 1,), "float32", one_chip)},
        {"perUser": rows(PU, "int32")}, {"perUser": rows(PU, "float32")},
        fixed_parts=(("fixed", "global"),), re_parts=(("perUser", "user"),),
    ).compile()


# --------------------------------------------- four chips, --devices 0 path


def test_spmd_value_and_grad_compiles_on_four_devices(four_chips):
    """The explicit shard_map + psum objective: one all-reduce of (value,
    gradient) per evaluation over a described 2x2 v5e."""
    from photon_tpu.parallel.spmd_objective import SpmdGLMObjective

    mesh, rows, replicated = four_chips
    spmd = SpmdGLMObjective(
        obj=_problem(FIXED).objective(), batch=None, mesh=mesh)

    def value_and_grad(w, batch):
        return dataclasses.replace(spmd, batch=batch).value_and_grad(w)

    compiled = jax.jit(value_and_grad).lower(
        _sds((D,), "float32", replicated), _fixed_batch(rows, fast=False),
    ).compile()
    assert "all-reduce" in compiled.as_text()


def test_fit_data_parallel_compiles_on_four_devices(four_chips):
    """The default mesh path of the training driver (GSPMD): the whole
    L-BFGS program with the batch row-sharded and coefficients replicated."""
    from photon_tpu.parallel.data_parallel import _fit_dp_jitted

    mesh, rows, replicated = four_chips
    vec = _sds((D,), "float32", replicated)
    compiled = _fit_dp_jitted.lower(
        _problem(FIXED), replicated, _fixed_batch(rows, fast=False), vec,
        vec, None, None).compile()
    assert "all-reduce" in compiled.as_text()
    # Each device holds a quarter of the rows, not all of them.
    per_device_args = compiled.memory_analysis().argument_size_in_bytes
    assert per_device_args < (N * K * 8 + 3 * N * 4) / 4 + 3 * D * 4


def test_entity_sharded_bucket_solver_compiles_on_four_devices(four_chips):
    from photon_tpu.game.newton_re import fit_bucket_newton

    _, rows, _ = four_chips
    batches, w0, mask = _bucket(rows, E)
    fit_bucket_newton.lower(
        _problem(PER_USER), batches, w0, mask, None).compile()


# The factored cell's projection step: 9,997,911 rows padded to whole
# chunks of the flat rows, 26,744 items, 69,246 users' latent vectors.
FACTORED_ROWS, FACTORED_ITEMS, FACTORED_SLOTS = 10485760, 26744, 69246


@pytest.mark.parametrize("latent", [8, 32])
def test_the_factored_projection_step_compiles_over_rows_sorted_by_item(
        one_chip, compiled_kernel, latent):
    """``_projection_step`` over ``SortedRows`` at the factored cell's
    shapes: the chip's compiler takes both kernels inside the L-BFGS loop,
    and the rows' latent vectors are laid ``[p, rows]`` and never held as
    ``[rows, p]`` (5.4 GB at 32, padded to 128 lanes). Read here at 32:
    1.98 GB of temporaries, the 1.34 GB of ``Bᵀ`` among them."""
    from photon_tpu.functions.problem import GLMOptimizationProblem
    from photon_tpu.game import factored_random_effect as f
    from photon_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.types import TaskType

    sh = one_chip
    r, chunks = FACTORED_ROWS, FACTORED_ROWS // CHUNK
    plan = f.SortedRows(
        perm=_sds((r,), "int32", sh), col=_sds((chunks, CHUNK), "int32", sh),
        val=_sds((chunks, CHUNK), "float32", sh),
        slot=_sds((r,), "int32", sh), weights=_sds((r,), "float32", sh),
        labels=_sds((r,), "float32", sh),
        windows=_sds((chunks,), "int32", sh), window_passes=chunks)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer_type=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=3),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=10.0)
    compiled = f._projection_step.lower(
        problem, _sds((FACTORED_ITEMS, latent), "float32", sh), plan,
        _sds((9997911,), "float32", sh),
        _sds((FACTORED_SLOTS, latent), "float32", sh)).compile()
    text = compiled.as_text()
    assert "factored_sorted_times" in text
    assert "factored_sorted_scatter" in text
    assert f"f32[{r},{latent}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


# ----------------------------------- what the compiler refuses (ROADMAP R-a3)


@pytest.mark.xfail(
    strict=True,
    reason="what the deleted Pallas kernels' central lookup asked of the "
           "hardware: 'Mosaic failed to compile TPU kernel: Not implemented: "
           "Multiple source vregs along gather dimension' — the hardware "
           "gather reads within one 8x128 register, not across a [2048, 128] "
           "table. A strict xfail: the day the compiler accepts it, this says")
def test_gather_across_a_2048_row_vmem_table_is_refused(one_chip):
    from jax.experimental import pallas as pl

    nb = 2048    # a coefficient table of 2048 x 128 = 256K features in VMEM

    def kernel(table_ref, idx_ref, out_ref):
        out_ref[:] = jnp.take_along_axis(
            table_ref[:], idx_ref[:], axis=0, mode="promise_in_bounds")

    call = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((nb, 128), jnp.float32))
    jax.jit(call).lower(
        _sds((nb, 128), "float32", one_chip),
        _sds((nb, 128), "int32", one_chip)).compile()
