"""Seeded weights, made on the device in one jitted call.

The tree has the layout the system under test loads (matrices stored
(in, out) and stacked over layers); the reference reads the same tree.
Nothing is trained or read from disk.  ``sparse24`` keeps, in every
group of four consecutive inputs of each layer matrix, the two entries
of largest magnitude (ties to the lower index): 2:4 by magnitude.

A configuration's reference declares the layout as
``layout(dm, bias) -> {path: (shape, role)}`` or ``(shape, role,
dtype)``, bf16 where no dtype is given.  The roles:

* ``scale``: ``1 + 0.1 z`` (a norm's gain);
* ``embed``: ``z``, or unit-gain logits ``z / sqrt(d)`` under a tied head;
* ``dense_matrix``: unit gain over the input axis ``-2`` (an untied
  head, a router, a gate), never masked;
* ``matrix``: any rank of 3 or more, stacked over leading axes, input
  axis ``-2``: unit gain over it, and 2:4 along it under ``sparse24``;
* ``vector``: ``0.1 z`` (a bias).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from lib import harness


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def mask24(w: jnp.ndarray) -> jnp.ndarray:
    """2:4 by magnitude along the input axis of (..., in, out)."""
    *lead, n, m = w.shape
    a = jnp.abs(w).reshape(*lead, n // 4, 4, m)      # exact in bf16
    col = [a[..., i, :] for i in range(4)]
    keep = []
    for i in range(4):       # entries that outrank entry i of its group
        above = sum(((col[j] > col[i]) | ((col[j] == col[i]) & (j < i)))
                    .astype(jnp.int32) for j in range(4) if j != i)
        keep.append(above < 2)
    keep = jnp.stack(keep, axis=-2).reshape(w.shape)
    return jnp.where(keep, w, jnp.zeros_like(w))


ROLES = ("scale", "embed", "matrix", "dense_matrix", "vector")


def leaves(dm: Dict[str, Any], bias: bool, layout: Callable
           ) -> Tuple[Tuple[str, Tuple[int, ...], str, str], ...]:
    """(path, shape, role, dtype) of every leaf, sorted by path."""
    out = []
    for path, (shape, role, *dtype) in sorted(layout(dm, bias).items()):
        if role not in ROLES:
            raise ValueError(f"{path}: role {role!r} is none of {ROLES}")
        if role == "matrix" and len(shape) < 3:
            raise ValueError(f"{path}: a matrix is stacked, rank 3 or more")
        out.append((path, tuple(shape), role,
                    dtype[0] if dtype else "bfloat16"))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("leaves", "sparse24", "tie"))
def _make(key, leaves, sparse24: bool, tie: bool):
    out = {}
    for i, (path, shp, role, dtype) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shp, dtype)
        if role == "scale":
            w = 1.0 + 0.1 * z
        elif role == "embed":        # a tied head keeps unit-gain logits
            w = z * (shp[-1] ** -0.5) if tie else z
        elif role == "vector":
            w = 0.1 * z
        else:                                         # matrices: unit gain
            w = z * (shp[-2] ** -0.5)
            if sparse24 and role == "matrix":
                w = mask24(w)
        out[path] = w.astype(dtype)
    return out


def make(dm: Dict[str, Any], bias: bool, seed: int, sparse24: bool,
         layout: Callable):
    """The whole nested parameter tree from ``seed``, on the default
    device, laid out by ``layout`` (see above)."""
    flat = _make(seed_key(seed), leaves(dm, bias, layout), sparse24,
                 dm.get("tie", False))
    tree: Dict[str, Any] = {}
    for path, w in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = w
    return tree


def for_config(conf: Dict[str, Any], seed: int, sparse24: bool = False):
    """``make`` for a configuration file: its reference's sizes and
    layout, biases as the file states them."""
    ref = harness.reference(conf)
    return make(ref.dims(conf), conf.get("enable_bias", False), seed,
                sparse24, ref.layout)
