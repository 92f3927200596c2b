"""Packed-2:4 weight store for memory-bound serving.

``pack_tree`` walks a param pytree and replaces every 2-D weight whose
paper-layout transpose satisfies the 2:4 pattern with the packed dict
``{"vals", "meta"}`` consumed transparently by ``models.common.dense``
(spmm24 kernel).  Decode-time weight traffic drops to 0.625x — the TPU
adaptation of the paper's 2:4 motivation (DESIGN.md §2).

Embeddings, norms, vectors, stacked expert tensors and anything not
actually 2:4-sparse are left dense.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import SparsitySpec, satisfies
from repro.kernels import ops as kops
from repro.utils.tree import tree_map_with_path

_SPEC = SparsitySpec(kind="nm", n=2, m=4)


def _pattern_ok(w_paper: np.ndarray) -> bool:
    """w_paper (..., out, in): 2:4 along the input dim and mostly sparse."""
    groups = w_paper.reshape(w_paper.shape[:-1] + (w_paper.shape[-1] // 4, 4))
    return bool(((groups != 0).sum(axis=-1) <= 2).all()) and \
        float((w_paper == 0).mean()) >= 0.45


def _packable(path: str, w: Any) -> bool:
    if not hasattr(w, "ndim") or w.ndim not in (2, 3):
        return False
    if "embed" in path or "norm" in path or "conv" in path \
            or path.endswith(("scale", "bias")):
        return False
    if w.shape[-2] % 4 != 0:   # input dim (in, out layout) must be whole groups
        return False
    if min(w.shape[-2:]) < 8:  # layer-stacked bias vectors (L, d) are 2-D too
        return False
    wn = np.asarray(w, np.float32)
    if not wn.any():           # all-zero (fresh-init) tensors are not "2:4"
        return False
    w_paper = wn.T if w.ndim == 2 else wn.transpose(0, 2, 1)  # (L, out, in)
    return _pattern_ok(w_paper)


def pack_tree(params: Any, dtype: Any = jnp.bfloat16) -> Tuple[Any, dict]:
    """Returns (packed params, stats {packed_ops, dense_bytes, packed_bytes}).

    2-D weights (in, out) pack to {"vals" (out,in/2), "meta" (out,in/4)};
    layer-stacked 3-D weights (L, in, out) pack per-slice via vmap — the
    serving scan then slices the packed leaves exactly like dense ones.

    ``dtype`` is the packed-value storage dtype (bf16, the TPU serving
    default); ``dtype=None`` keeps each weight's own dtype, making the
    packing bitwise-lossless — the serve engine's fast path uses this so
    packed logits match the dense-matmul logits exactly.
    """
    stats = {"packed_ops": 0, "dense_bytes": 0, "packed_bytes": 0}

    def visit(path, w):
        if _packable(path, w):
            wt = jnp.asarray(w)
            wt = wt if dtype is None else wt.astype(dtype)
            if w.ndim == 2:
                vals, meta = kops.pack24(wt.T)
            else:
                import jax
                vals, meta = jax.vmap(kops.pack24)(wt.transpose(0, 2, 1))
            itemsize = jnp.dtype(vals.dtype).itemsize
            stats["packed_ops"] += 1 if w.ndim == 2 else w.shape[0]
            stats["dense_bytes"] += w.size * itemsize
            stats["packed_bytes"] += vals.size * itemsize + meta.size
            return {"vals": vals, "meta": meta}
        return w

    return tree_map_with_path(visit, params), stats


def is_packed_leaf(node: Any) -> bool:
    return (isinstance(node, dict) and len(node) == 2
            and "vals" in node and "meta" in node)


def count_packed(params: Any) -> int:
    """Number of packed-2:4 operator leaves in a param tree."""

    def rec(node) -> int:
        if is_packed_leaf(node):
            return node["vals"].shape[0] if node["vals"].ndim == 3 else 1
        if isinstance(node, dict):
            return sum(rec(v) for v in node.values())
        if isinstance(node, (list, tuple)):
            return sum(rec(v) for v in node)
        return 0

    return rec(params)


def decode_view(params: Any, sharded: bool = False) -> Any:
    """The representation the decode step should *compute* with.

    On TPU: identity — packed leaves feed the spmm24 / fused-MLP
    kernels, which is the whole point of packing (0.625x weight traffic).
    ``sharded=True`` (a step partitioned over a mesh) takes the dense
    view on every backend: JAX cannot partition a Mosaic kernel outside
    ``shard_map``, and the sharding rules replicate packed stores, so each
    chip would read 0.625x of every weight where the dense view sharded
    over "model" reads 1/model of it.

    On CPU there is no packed-matmul hardware to win on, and unpacking
    inside the jitted per-token step (or interpreting the Pallas kernel)
    made packed serving ~2x *slower* than dense — the measured
    BENCH_serve regression.  So the unpack happens HERE, once, at
    construction: the returned tree is the bitwise-lossless dense view
    (pack_tree with ``dtype=None`` keeps values exactly), the caller
    keeps the packed tree for accounting (``packed_bytes`` in
    serve_bench's modeled roofline), and the hot loop runs plain dense
    matmuls.  Identity when nothing is packed.
    """
    import jax
    if jax.default_backend() == "tpu" and not sharded:
        return params
    n = count_packed(params)
    if n == 0:
        return params
    from repro.utils import get_logger
    get_logger("serve").info(
        "%s: caching dense decode view of %d packed operators "
        "(packed tree kept for accounting)",
        "sharded step" if sharded else "CPU backend", n)
    return unpack_tree(params)


def unpack_tree(params: Any) -> Any:
    """Inverse of pack_tree (packed dicts -> dense (in, out))."""

    def rec(node):
        if isinstance(node, dict):
            if is_packed_leaf(node):
                n = node["vals"].shape[-1] * 2
                if node["vals"].ndim == 3:
                    import jax
                    dense = jax.vmap(lambda v, m: kops.unpack24(v, m, n))(
                        node["vals"], node["meta"])
                    return dense.transpose(0, 2, 1)
                return kops.unpack24(node["vals"], node["meta"], n).T
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            seq = [rec(v) for v in node]
            return type(node)(seq) if isinstance(node, tuple) else seq
        return node

    return rec(params)
