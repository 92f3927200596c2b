"""Seeded weights, made on the device in one jitted call, in bf16.

The tree has the layout the system under test loads (matrices stored
(in, out) and stacked over layers); the reference reads the same tree.
Nothing is trained or read from disk.  ``sparse24`` keeps, in every
group of four consecutive inputs of each layer matrix, the two entries
of largest magnitude (ties to the lower index): 2:4 by magnitude.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def shapes(dm: Dict[str, Any], bias: bool) -> Dict[str, Tuple[int, ...]]:
    """Leaf path -> shape of the parameter tree for reference sizes ``dm``."""
    L, d, f, V = dm["layers"], dm["d"], dm["d_ff"], dm["vocab"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    out = {"embed": (V, d)} if dm["tie"] else {"embed": (V, d), "head": (d, V)}
    norms = ("scale", "bias") if dm["norm"] == "layernorm" else ("scale",)
    for n in norms:
        out[f"layers/ln1/{n}"] = out[f"layers/ln2/{n}"] = (L, d)
        out[f"final_norm/{n}"] = (d,)
    out.update({"layers/attn/wq": (L, d, q), "layers/attn/wk": (L, d, kv),
                "layers/attn/wv": (L, d, kv), "layers/attn/wo": (L, q, d)})
    if bias:
        out.update({"layers/attn/bq": (L, q), "layers/attn/bk": (L, kv),
                    "layers/attn/bv": (L, kv)})
    if dm["act"] == "silu":
        out.update({"layers/mlp/gate": (L, d, f), "layers/mlp/up": (L, d, f),
                    "layers/mlp/down": (L, f, d)})
    else:
        out.update({"layers/mlp/fc1": (L, d, f), "layers/mlp/b1": (L, f),
                    "layers/mlp/fc2": (L, f, d), "layers/mlp/b2": (L, d)})
    return out


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


@functools.partial(jax.jit, static_argnames=("leaves", "sparse24", "tie"))
def _make(key, leaves, sparse24: bool, tie: bool):
    out = {}
    for i, (path, shp) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shp, jnp.bfloat16)
        if path.endswith("scale"):
            w = 1.0 + 0.1 * z
        elif path == "embed":        # a tied head keeps unit-gain logits
            w = z * (shp[-1] ** -0.5) if tie else z
        elif len(shp) == 3 or path == "head":       # matrices: unit gain
            w = z * (shp[-2] ** -0.5)
            if sparse24 and len(shp) == 3:
                w = mask24(w)
        else:                                         # biases
            w = 0.1 * z
        out[path] = w.astype(jnp.bfloat16)
    return out


def make(dm: Dict[str, Any], bias: bool, seed: int, sparse24: bool = False):
    """The whole nested parameter tree from ``seed``, on the default device."""
    leaves = tuple(sorted(shapes(dm, bias).items()))
    flat = _make(seed_key(seed), leaves, sparse24, dm["tie"])
    tree: Dict[str, Any] = {}
    for path, w in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = w
    return tree
