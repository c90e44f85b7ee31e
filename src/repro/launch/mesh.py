"""Production mesh definition (a function — importing never touches devices)."""
from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).

    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); the ``pod`` axis
    carries data parallelism by default (lowest bisection bandwidth -> lowest
    communication volume), and optionally pipeline stages (see
    ``repro.distributed.pipeline``).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 1):
    """Small mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    if model * data > n:
        model, data = 1, min(data, n)
    return compat.make_mesh((data, model), ("data", "model"))
