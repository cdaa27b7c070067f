"""Where compare-select stops paying against the gather / scatter-add:
``_bucket_scores`` and ``_dense_design`` under both formulations at given
bucket shapes, whatever ``SELECT_MAX_COLUMNS`` says (PERF.md §6, PR 33).

    python scripts/entity_pick_check.py <mode> E,S,K,P,W [E,S,K,P,W ...]

``compile``: compile for a described v5e, no chip needed (seconds,
temporaries). ``cpu``: the CPU compiler's temporaries. ``run``: on the
chip, compile and run (median of 7) and the gap between the two. W is 0 for
both formulations, 1 for the compare-select alone (the gather compiles for
minutes at millions of slots).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data import random_effect as red
from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.game import newton_re

mode = sys.argv[1]
shapes = [tuple(int(x) for x in a.split(",")) for a in sys.argv[2:]]


def force(select: bool):
    v = 10 ** 9 if select else 0
    red.SELECT_MAX_COLUMNS = v
    newton_re.SELECT_MAX_COLUMNS = v


def functions():
    raw = red._bucket_scores.__wrapped__
    scorer = jax.jit(lambda i, v, c: raw(i, v, c))
    design = jax.jit(lambda b: newton_re._dense_design(b, jnp.float32)[0])
    return scorer, design


def out(**kw):
    print(json.dumps(kw), flush=True)


if mode in ("compile", "cpu"):
    if mode == "compile":
        from jax.experimental import topologies
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        from jax.sharding import SingleDeviceSharding
        sh = SingleDeviceSharding(topo.devices[0])
    else:
        sh = None

    def sds(shape, dtype):
        if sh is None:
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sh)

    for e, s, k, p, which in shapes:
        # which: 0 both formulations, 1 select only
        for select in ((True, False) if which == 0 else (True,)):
            force(select)
            scorer, design = functions()
            b = LabeledBatch(
                features=SparseFeatures(idx=sds((e, s, k), "int32"),
                                        val=sds((e, s, k), "float32"), dim=p),
                labels=sds((e, s), "float32"), offsets=sds((e, s), "float32"),
                weights=sds((e, s), "float32"))
            t0 = time.perf_counter()
            c1 = scorer.lower(sds((e, s, k), "int32"), sds((e, s, k), "float32"),
                              sds((e, p), "float32")).compile()
            t1 = time.perf_counter()
            c2 = design.lower(b).compile()
            t2 = time.perf_counter()
            out(mode=mode, e=e, s=s, k=k, p=p, select=select,
                scorer_compile_s=round(t1 - t0, 2),
                design_compile_s=round(t2 - t1, 2),
                scorer_temp_gb=c1.memory_analysis().temp_size_in_bytes / 1e9,
                design_temp_gb=c2.memory_analysis().temp_size_in_bytes / 1e9)
else:
    out(device=jax.devices()[0].device_kind)
    rng = np.random.default_rng(0)
    for e, s, k, p, which in shapes:
        idx = jnp.asarray(rng.integers(0, p + 1, (e, s, k), dtype=np.int32))
        val = jnp.asarray(rng.standard_normal((e, s, k), dtype=np.float32))
        coefs = jnp.asarray(rng.standard_normal((e, p), dtype=np.float32))
        z = jnp.zeros((e, s), jnp.float32)
        b = LabeledBatch(features=SparseFeatures(idx=idx, val=val, dim=p),
                         labels=z, offsets=z, weights=z)
        got = {}
        for select in ((True, False) if which == 0 else (True,)):
            force(select)
            scorer, design = functions()
            row = dict(e=e, s=s, k=k, p=p, select=select)
            for name, f, args in (("scorer", scorer, (idx, val, coefs)),
                                  ("design", design, (b,))):
                t0 = time.perf_counter()
                c = f.lower(*args).compile()
                row[name + "_compile_s"] = round(time.perf_counter() - t0, 2)
                r = c(*args)
                r.block_until_ready()
                ts = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    c(*args).block_until_ready()
                    ts.append(time.perf_counter() - t0)
                row[name + "_run_ms"] = round(1e3 * float(np.median(ts)), 3)
                row[name + "_temp_gb"] = c.memory_analysis().temp_size_in_bytes / 1e9
                got.setdefault(name, []).append(np.asarray(r))
                del r
            out(**row)
        for name, rs in got.items():
            if len(rs) == 2:
                out(e=e, s=s, k=k, p=p, what=name,
                    max_gap=float(np.max(np.abs(rs[0] - rs[1]))))
