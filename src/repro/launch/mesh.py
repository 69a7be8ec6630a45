"""Production mesh construction.

A function, not a module constant: importing this module must never touch
jax device state (smoke tests run on 1 real CPU device; only dryrun.py
requests 512 virtual devices via XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever devices exist, data x model (for tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"))
