#!/usr/bin/env python3
"""TRON's own faults, planted under the timed path as ``faults.py`` plants
the others: each has to read ``correct`` false in a cell whose fixed effect
is solved by TRON (``PERF.md`` §2).

* ``one_cg_step``: the CG solve cut to one step a trust-region iteration,
  so every step is a scaled steepest-descent step;
* ``unweighted_hvp``: the Hessian-vector product without the loss's
  curvature weights, ``X^T X v + l2 v`` where ``X^T (d2 * X v) + l2 v`` is
  stated;
* ``accepts_all``: every trial step taken, whatever its ``rho``.

The last two change code that the jitted solve has already traced, so they
drop the solve's traced programs on the way in and on the way out.

Run as a script it is ``chip_readings.py`` with these three added to the
faults it plants:

    python3 benchmarks/tests/faults_tron.py glm_fit_tron --program 100-119 \\
        --program-bf16 200-202 --faults 300-302
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.tests import faults


def one_cg_step():
    return faults._with_optimizer(max_cg_iterations=lambda n: 1)


@contextlib.contextmanager
def _retraced():
    """The fixed-effect solve traced anew inside the block and after it."""
    from photon_tpu.functions import problem

    problem._fit_jitted.clear_cache()
    try:
        yield
    finally:
        problem._fit_jitted.clear_cache()


@contextlib.contextmanager
def unweighted_hvp():
    import jax.numpy as jnp

    from photon_tpu.functions.objective import GLMObjective

    original = GLMObjective.bind_hvp_at

    def bind_hvp_at(self, batch):
        flat = dataclasses.replace(self.loss, d2=lambda z, y: jnp.ones_like(z))
        return original(dataclasses.replace(self, loss=flat), batch)

    GLMObjective.bind_hvp_at = bind_hvp_at
    try:
        with _retraced():
            yield
    finally:
        GLMObjective.bind_hvp_at = original


@contextlib.contextmanager
def accepts_all():
    from photon_tpu.optim import tron

    original = tron._ETA0
    tron._ETA0 = -float("inf")
    try:
        with _retraced():
            yield
    finally:
        tron._ETA0 = original


FAULTS = {
    "one_cg_step": one_cg_step,
    "unweighted_hvp": unweighted_hvp,
    "accepts_all": accepts_all,
}


if __name__ == "__main__":
    from benchmarks.tests import chip_readings

    faults.FAULTS.update(FAULTS)
    sys.exit(chip_readings.main())
