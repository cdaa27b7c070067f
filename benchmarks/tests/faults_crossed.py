#!/usr/bin/env python3
"""The faults only two crossed per-entity coordinates can show, planted
under the timed path as ``faults.py`` and ``faults_ragged.py`` plant the
others (``PERF.md`` §2). The per-movie coordinate is the one whose
dataset's ``re_type`` is ``MOVIE``:

* ``movie_residual_without_users``: the per-movie step trains against a
  residual that leaves the per-user scores out (the offsets it is handed
  less the latest per-user scores): a descent that forgot one of the other
  two coordinates;
* ``unchanged_movie``: the per-movie coordinate returns its start
  unchanged (zero coefficients on the first step), the per-user one sound;
* ``unseen_movie_scored``: a validation row of a movie the model never saw
  is scored with a seen movie's intercept (the largest of them) where it
  has to score zero;
* ``single_row_movies_unsolved``: the size class of movies of one row
  returns its start unchanged.

Run as a script it makes ``faults_ragged.py``'s readings for a cell of
kind ``fit_crossed`` (that kind's generator, estimator and comparison; the
same protocol, workers and options), with these four added to the faults
it plants:

    python3 benchmarks/tests/faults_crossed.py game_fit_crossed \\
        --faults 300 --program 301-305
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.tests import faults, faults_ragged

MOVIE = "movieId"


def _is_movie(coordinate) -> bool:
    return coordinate.dataset.re_type == MOVIE


def movie_residual_without_users():
    from photon_tpu.game import coordinates as co

    latest: dict = {}

    def wrapper(original):
        def train(self, offsets, init=None):
            if _is_movie(self) and "users" in latest:
                offsets = offsets - latest["users"]
            model, result = original(self, offsets, init)
            if not _is_movie(self):
                # (not ``self.score``: the harness's wrapper around it
                # would take this for the descent's own scoring)
                latest["users"] = model.score_dataset(self._data())
            return model, result
        return train

    return faults._patched(co.RandomEffectCoordinate, wrapper)


def _movie_start(pick):
    """The per-movie step's answer with the buckets ``pick(dataset)``
    names left at their start."""
    import jax.numpy as jnp

    from photon_tpu.game import coordinates as co

    def wrapper(original):
        def train(self, offsets, init=None):
            model, result = original(self, offsets, init)
            if not _is_movie(self):
                return model, result
            coefs = list(model.bucket_coefs)
            for b in pick(self.dataset):
                coefs[b] = (jnp.zeros_like(coefs[b]) if init is None
                            else init.bucket_coefs[b])
            return dataclasses.replace(model, bucket_coefs=coefs), result
        return train

    return faults._patched(co.RandomEffectCoordinate, wrapper)


def unchanged_movie():
    return _movie_start(lambda dataset: range(len(dataset.buckets)))


def single_row_movies_unsolved():
    return _movie_start(lambda dataset: [
        b for b, bucket in enumerate(dataset.buckets)
        if bucket.max_samples == 1])


@contextlib.contextmanager
def unseen_movie_scored():
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.game.random_effect import RandomEffectModel

    original = RandomEffectModel.project_to

    def project_to(self, dataset):
        stacks = original(self, dataset)
        if self.re_type != MOVIE:
            return stacks
        seen = np.concatenate([np.asarray(c).ravel()
                               for c in self.bucket_coefs])
        borrowed = seen[np.argmax(np.abs(seen))]
        known = set(self.entity_keys)
        out = []
        for bucket, stack in zip(dataset.buckets, stacks):
            ids = np.asarray(bucket.entity_ids)
            unseen = np.array([dataset.entity_keys[i] not in known
                               for i in ids])
            out.append(jnp.where(jnp.asarray(unseen)[:, None],
                                 jnp.asarray(borrowed, stack.dtype), stack))
        return out

    RandomEffectModel.project_to = project_to
    try:
        yield
    finally:
        RandomEffectModel.project_to = original


FAULTS = {
    "movie_residual_without_users": movie_residual_without_users,
    "unchanged_movie": unchanged_movie,
    "unseen_movie_scored": unseen_movie_scored,
    "single_row_movies_unsolved": single_row_movies_unsolved,
}


# ------------------------------------------------- the readings on the chip
#
# ``faults_ragged.py``'s protocol and its ``main``, with this kind's
# generator, estimator, steps and comparison in the places where that file
# names its own (a ``benchmark`` issue should let its ``main`` take the
# kind: PERF.md §7).


def _dataset(config: dict, seed: int):
    from benchmarks.kinds import fit_crossed

    return fit_crossed.generate(config["data"], seed)


def compare(config: dict, seed: int, path: str) -> dict:
    """In a worker: ``faults_ragged.compare`` over this kind's data and
    comparison."""
    from benchmarks.kinds import fit_crossed, fit_ragged

    faults_ragged._dataset = _dataset
    fit_ragged.check = fit_crossed.check
    return faults_ragged.compare(config, seed, path)


def main() -> int:
    from benchmarks.kinds import fit, fit_crossed
    from benchmarks.tests import test_fit_kind_crossed, test_fit_kind_ragged

    fit.build = fit_crossed.build
    fit._plain_steps = fit_crossed._plain_steps
    faults_ragged._dataset = _dataset
    faults_ragged.compare = compare
    faults_ragged.FAULTS = dict(faults_ragged.FAULTS, **FAULTS)
    test_fit_kind_ragged.tiny_ragged = test_fit_kind_crossed.tiny_crossed
    return faults_ragged.main()


if __name__ == "__main__":
    sys.exit(main())
