"""Seconds a fit spends in the latent steps of its factored coordinate
(``factored.latent``: the projected rows laid into the buckets and every
entity's latent problem solved, ending in the read of the solves'
counts), per fit."""
from benchmarks.layer_metrics import _factored


def read(state: dict):
    return _factored.seconds_per_fit(state, _factored.LATENT)
