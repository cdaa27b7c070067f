"""The control and the faults, planted under the timed path, for the tests
and the chip readings that have to see ``correct`` come out false (PERF.md
§2, "How `correct` is decided" steps 2 and 3).

Each is a context manager that patches the program's coordinate classes;
the harness's own wrappers go on top, so a run sees the faulty program as
if it were the program. They are the faults a one-chip training cell can
have:

* ``unchanged``: a coordinate step that returns its state unchanged (zero
  coefficients on the first step), for the coordinate kind named;
* ``half_batch``: the fixed effect trains on half of the rows, the rest
  weighted up so that the sums keep their size (the mean over the rest);
* ``altered_validation``: the validation answer altered where it is
  produced, the scores of one validation row in 64 with the wrong sign;
* ``altered``: the fixed effect's answer altered where it is produced,
  every coefficient with the wrong sign. (Sound fits stop up to a twentieth
  of the way from their optimum, PERF.md §2, so an alteration has to be
  larger than that to be told from a sound answer by anything.);
* ``short_memory``: the fixed effect's L-BFGS keeps one curvature pair
  where the configuration states ten, so its updates go wrong from the
  third iteration on;
* ``stops_early``: the fixed effect's solve stalls, a fifth of the stated
  iterations and no more.

The control, ``bfloat16_values``, is the program's own lower-precision
path switched on where the program switches it (``PHOTON_VALUE_DTYPE``
read in ``SparseFeatures.with_accelerator_paths``): feature values stored
in bfloat16 and widened on load.
"""
from __future__ import annotations

import contextlib
import dataclasses


def _coefficient_leaf(model, fn):
    """``model`` with its coefficients mapped through ``fn``."""
    if hasattr(model, "bucket_coefs"):
        return dataclasses.replace(
            model, bucket_coefs=[fn(c) for c in model.bucket_coefs])
    glm = model.model
    means = fn(glm.coefficients.means)
    return dataclasses.replace(model, model=dataclasses.replace(
        glm, coefficients=dataclasses.replace(glm.coefficients, means=means)))


@contextlib.contextmanager
def _patched(cls, wrapper):
    original = cls.train
    cls.train = wrapper(original)
    try:
        yield
    finally:
        cls.train = original


def unchanged(kind: str = "fixed"):
    import jax.numpy as jnp

    from photon_tpu.game import coordinates as co

    cls = (co.FixedEffectCoordinate if kind == "fixed"
           else co.RandomEffectCoordinate)

    def wrapper(original):
        def train(self, offsets, init=None):
            model, result = original(self, offsets, init)
            if init is not None:
                return init, result
            return _coefficient_leaf(model, jnp.zeros_like), result
        return train

    return _patched(cls, wrapper)


def half_batch():
    import jax.numpy as jnp

    from photon_tpu.game import coordinates as co

    def wrapper(original):
        def train(self, offsets, init=None):
            w = self.batch.weights
            kept = jnp.where(jnp.arange(w.shape[0]) < w.shape[0] // 2,
                             2.0, 0.0).astype(w.dtype)
            half = dataclasses.replace(self, batch=dataclasses.replace(
                self.batch, weights=w * kept))
            return original(half, offsets, init)
        return train

    return _patched(co.FixedEffectCoordinate, wrapper)


def altered(scale: float = -1.0):
    from photon_tpu.game import coordinates as co

    def wrapper(original):
        def train(self, offsets, init=None):
            model, result = original(self, offsets, init)
            return _coefficient_leaf(model, lambda c: c * scale), result
        return train

    return _patched(co.FixedEffectCoordinate, wrapper)


@contextlib.contextmanager
def altered_validation():
    import jax.numpy as jnp

    from photon_tpu.evaluation import EvaluationSuite

    original = EvaluationSuite.evaluate

    def evaluate(self, scores, *args, **kwargs):
        flip = jnp.where(jnp.arange(scores.shape[0]) % 64 == 0, -1.0, 1.0)
        return original(self, scores * flip.astype(scores.dtype), *args, **kwargs)

    EvaluationSuite.evaluate = evaluate
    try:
        yield
    finally:
        EvaluationSuite.evaluate = original


def _with_optimizer(**changes):
    """The fixed effect solved under an optimizer configuration changed
    from the stated one."""
    from photon_tpu.game import coordinates as co

    def wrapper(original):
        def train(self, offsets, init=None):
            cfg = self.problem.optimizer_config
            new = {k: v(getattr(cfg, k)) for k, v in changes.items()}
            problem = dataclasses.replace(
                self.problem, optimizer_config=dataclasses.replace(cfg, **new))
            return original(dataclasses.replace(self, problem=problem),
                            offsets, init)
        return train

    return _patched(co.FixedEffectCoordinate, wrapper)


def short_memory():
    return _with_optimizer(history_length=lambda m: 1)


def stops_early():
    return _with_optimizer(max_iterations=lambda n: max(1, n // 5))


@contextlib.contextmanager
def bfloat16_values():
    import jax.numpy as jnp

    from photon_tpu.data.batch import SparseFeatures

    original = SparseFeatures.with_accelerator_paths

    def with_accelerator_paths(self):
        return original(self).with_value_dtype(jnp.bfloat16)

    SparseFeatures.with_accelerator_paths = with_accelerator_paths
    try:
        yield
    finally:
        SparseFeatures.with_accelerator_paths = original


CONTROL = bfloat16_values

FAULTS = {
    "unchanged_fixed": lambda: unchanged("fixed"),
    "unchanged_random": lambda: unchanged("random"),
    "half_batch": half_batch,
    "altered": altered,
    "altered_validation": altered_validation,
    "short_memory": short_memory,
    "stops_early": stops_early,
}
