"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before calling.

Axis roles (DESIGN.md §5, §10):
    pod   — outer data-parallel axis (or pipeline stages with --pipeline)
    data  — within-pod data parallelism (+ layer-unit queue for pruning,
            calibration/eval batch sharding)
    model — tensor/expert parallelism (+ row-parallel FISTA, decode TP)
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh with Auto axes: the model code shards through NamedSharding
    and shard_map regions (GSPMD), which Explicit axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def factor_debug_mesh(devices: int, multi_pod: bool = False
                      ) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Factor ``devices`` into the debug-mesh shape (pure, no jax state).

    Invariants (pinned in tests/test_mesh.py):
      * the shape's product is exactly ``devices`` — EVERY count builds,
        including 1, odd counts, and non-powers-of-two (6, 12);
      * "model" is the largest power-of-two divisor that does not exceed
        "data" (model^2 <= per-pod devices), so the model axis never
        dominates the data axis and never degenerates the data axis to 0.

    The seed implementation grew "model" while ``devices % (2*model)``
    held, which (a) divided by zero-sized data axes for devices < 4
    (``make_debug_mesh(1)`` -> a (0, 2) mesh) and (b) mis-factored
    2*odd counts under ``multi_pod`` (6 -> (2, 1, 2): product 4 != 6).
    """
    if devices < 1:
        raise ValueError(f"need >= 1 device, got {devices}")
    pod: Tuple[int, ...] = ()
    rest = devices
    if multi_pod:
        if devices % 2 != 0:
            raise ValueError(f"multi_pod needs an even device count, got {devices}")
        pod, rest = (2,), devices // 2
    model = 1
    while rest % (model * 2) == 0 and (model * 2) ** 2 <= rest:
        model *= 2
    shape = pod + (rest // model, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shape, axes


def make_debug_mesh(devices: int, multi_pod: bool = False):
    """Scaled-down mesh with the same axis names (tests / CI)."""
    shape, axes = factor_debug_mesh(devices, multi_pod=multi_pod)
    return make_mesh(shape, axes)
