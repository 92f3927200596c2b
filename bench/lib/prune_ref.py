"""Plain reference of the paper's layer-wise prune, for checking answers.

Independent of the system under test.  For one unit (decoder layer) it
computes, from a configuration's plain reference forward, the inputs X
(dense path) and X* (the unit's earlier operator groups already pruned)
of every linear operator, and from them:

* the error of any candidate weights, ``||X* Y - X W||_F / ||X W||_F``,
  evaluated directly from activations (no Gram cancellation);
* the Gram statistics G = X*^T X*, C = X^T X*, H = X^T X, h = ||X W||^2;
* the paper's Algorithm 1 (FISTA on the Gram form, lambda bisection on
  the rounding share, 2:4 rounding, Wanda warm start) as a reference
  solve.

``dtype=jnp.bfloat16`` computes everything in bf16 instead of float32
at HIGHEST: that is the control, the reference one precision lower.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: the order in which a unit's operators are pruned (paper Sec. 3.1):
#: operators that share an input form one group
GROUPS = {"gelu": (("attn/wq", "attn/wk", "attn/wv"), ("attn/wo",),
                   ("mlp/fc1",), ("mlp/fc2",)),
          "silu": (("attn/wq", "attn/wk", "attn/wv"), ("attn/wo",),
                   ("mlp/gate", "mlp/up"), ("mlp/down",))}


def get(tree: Dict[str, Any], path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def with_ops(unit: Dict[str, Any], ops: Dict[str, Any]) -> Dict[str, Any]:
    """A copy of ``unit`` with the weights at ``ops`` paths replaced."""
    out = jax.tree_util.tree_map(lambda a: a, unit)
    for path, w in ops.items():
        *parents, last = path.split("/")
        node = out
        for p in parents:
            node = node[p]
        node[last] = w
    return out


def _mm(a, b, dtype):
    if dtype == jnp.float32:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.matmul(a.astype(dtype), b.astype(dtype))


@jax.jit
def _nm_excess(w):
    g = (w.reshape(w.shape[:-2] + (w.shape[-2] // 4, 4, w.shape[-1])) != 0)
    return jnp.sum(g.sum(axis=-2) > 2)


def nm_violations(params: Dict[str, Any], act: str) -> int:
    """Groups of four consecutive inputs holding more than two nonzeros,
    over every prunable matrix of every layer."""
    return int(sum(int(_nm_excess(get(params["layers"], p)))
                   for grp in GROUPS[act] for p in grp))


_CAPS: Dict[Any, Any] = {}


def _captures(ref, dm, T: int, dtype):
    """Jitted (unit, x) -> captures of every operator input, cached."""
    key = (ref.__name__, tuple(sorted(dm.items())), T, dtype)
    if key not in _CAPS:
        pos = jnp.arange(T, dtype=jnp.int32)

        def caps(unit, x):
            def one(xx):
                cap = {}
                ref.layer(dm, unit, xx.astype(jnp.float32), pos, False, cap)
                return {k: v.astype(dtype) for k, v in cap.items()}
            return jax.vmap(one)(x)
        _CAPS[key] = jax.jit(caps)
    return _CAPS[key]


def group_stats(caps, unit_dense, mixed, xs, grp, targets,
                dtype=jnp.float32) -> Dict[str, Dict[str, Any]]:
    """Per operator of group ``grp``: {"num", "den", "G", "C", "H"} summed
    over the calibration batches ``xs`` ((B, T, d) unit inputs), where
    X comes from ``unit_dense`` and X* from ``mixed`` (the unit with its
    earlier groups pruned).  ``num`` is ||X* Y - X W||^2 for the
    candidate ``targets[op]`` = Y, and ``den`` is ||X W||^2 (= h)."""
    acc = {}
    fn = _acc32 if dtype == jnp.float32 else _acc16
    for x in xs:
        cd, cp = caps(unit_dense, x), caps(mixed, x)
        for p in grp:
            n = cd[p].shape[-1]
            prev = acc.get(p) or {
                "num": jnp.float32(0), "den": jnp.float32(0),
                "G": jnp.zeros((n, n), dtype), "C": jnp.zeros((n, n), dtype),
                "H": jnp.zeros((n, n), dtype)}
            acc[p] = fn(prev, cd[p], cp[p], get(unit_dense, p), targets[p])
    return acc


def unit_stats(ref, dm, unit_dense, unit_pruned, xs
               ) -> Dict[str, Dict[str, Any]]:
    """``group_stats`` of every operator of a unit pruned to
    ``unit_pruned``, groups in the order they are pruned."""
    caps = _captures(ref, dm, xs[0].shape[1], jnp.float32)
    groups, out = GROUPS[dm["act"]], {}
    for gi, grp in enumerate(groups):
        mixed = with_ops(unit_dense, {p: get(unit_pruned, p)
                                      for g in groups[:gi] for p in g})
        out.update(group_stats(caps, unit_dense, mixed, xs, grp,
                               {p: get(unit_pruned, p) for p in grp}))
    return out


def prune_unit(ref, dm, unit_dense, xs, dtype=jnp.bfloat16):
    """The reference put in the program's place: every operator of the
    unit pruned by ``solve`` from statistics computed in ``dtype``.
    Returns (pruned unit, [(key, error, rel_error)])."""
    caps = _captures(ref, dm, xs[0].shape[1], dtype)
    groups, unit, reports = GROUPS[dm["act"]], unit_dense, []
    for grp in groups:
        st = group_stats(caps, unit_dense, unit, xs, grp,
                         {p: get(unit_dense, p) for p in grp}, dtype)
        new = {}
        for p in grp:
            w = get(unit_dense, p).astype(jnp.float32).T
            y, rel = solve(w, st[p], dtype)
            new[p] = y.T.astype(get(unit_dense, p).dtype)
            h = float(st[p]["den"].astype(dtype))
            reports.append((p, rel * float(np.sqrt(max(h, 1e-30))), rel))
        unit = with_ops(unit, new)
    return unit, reports


@jax.jit
def _acc32(prev, xd, xp, w, y):
    return _acc(prev, xd, xp, w, y, jnp.float32)


@jax.jit
def _acc16(prev, xd, xp, w, y):
    return _acc(prev, xd, xp, w, y, jnp.bfloat16)


def _acc(prev, xd, xp, w, y, dtype):
    xd = xd.reshape(-1, xd.shape[-1])
    xp = xp.reshape(-1, xp.shape[-1])
    t = _mm(xd, w, dtype).astype(jnp.float32)
    r = _mm(xp, y, dtype).astype(jnp.float32) - t
    new = {"num": jnp.sum(r * r), "den": jnp.sum(t * t),
           "G": _mm(xp.T, xp, dtype), "C": _mm(xd.T, xp, dtype),
           "H": _mm(xd.T, xd, dtype)}
    return jax.tree_util.tree_map(lambda a, b: (a + b).astype(a.dtype),
                                  prev, new)


# ---------------------------------------------------------------------------
# Algorithm 1 of the paper, on the Gram form, in paper layout (out, in)
# ---------------------------------------------------------------------------
@jax.jit
def round24(y):
    """Keep the two largest |y| of every four consecutive inputs."""
    m, n = y.shape
    a = jnp.abs(y).reshape(m, n // 4, 4)
    r = jnp.argsort(jnp.argsort(-a, axis=-1, stable=True), axis=-1,
                    stable=True)
    return jnp.where((r < 2).reshape(m, n), y, jnp.zeros_like(y))


@functools.partial(jax.jit, static_argnames=("dtype",))
def _err(G, B, h, y, dtype):
    quad = jnp.sum(_mm(y, G, dtype).astype(jnp.float32) * y)
    cross = jnp.sum(y * B.astype(jnp.float32))
    return jnp.sqrt(jnp.maximum(quad - 2.0 * cross + h, 0.0))


@functools.partial(jax.jit, static_argnames=("iters", "dtype"))
def _fista(G, B, y0, lam, L, iters, dtype):
    def body(_, s):
        x, y, t = s
        grad = (_mm(y, G, dtype) - B).astype(jnp.float32)
        z = y - grad / L
        xn = jnp.sign(z) * jnp.maximum(jnp.abs(z) - lam / L, 0.0)
        tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        yn = xn + ((t - 1.0) / tn) * (xn - x)
        return (xn.astype(dtype).astype(jnp.float32),
                yn.astype(dtype).astype(jnp.float32), tn)
    x, _, _ = jax.lax.fori_loop(0, iters, body, (y0, y0, jnp.float32(1.0)))
    return x


@functools.partial(jax.jit, static_argnames=("dtype",))
def _setup(w, G, C, H, dtype):
    """B = W C, the Lipschitz bound 1.01 * lambda_max(G) by 64 power
    steps, and the Wanda 2:4 warm start |W| * ||x_j||."""
    B = _mm(w, C.astype(jnp.float32), dtype)

    def power(_, v):
        v = _mm(G, v, dtype).astype(jnp.float32)
        return v / jnp.linalg.norm(v)
    v = jax.lax.fori_loop(0, 64, power, jnp.ones((G.shape[0],), jnp.float32))
    L = 1.01 * jnp.sum(v * _mm(G, v, dtype).astype(jnp.float32))
    score = jnp.abs(w) * jnp.sqrt(jnp.maximum(
        jnp.diag(H).astype(jnp.float32), 0.0))[None, :]
    a = score.reshape(w.shape[0], -1, 4)
    r = jnp.argsort(jnp.argsort(-a, axis=-1, stable=True), axis=-1,
                    stable=True)
    return B, L, jnp.where((r < 2).reshape(w.shape), w, 0.0)


def solve(w, st, dtype=jnp.float32, K: int = 20, xi: float = 0.3,
          patience: int = 3, eps: float = 1e-3, max_outer: int = 40):
    """Reference 2:4 prune of ``w`` (out, in) from its Gram statistics.
    Returns (weights, relative error on the statistics)."""
    G = st["G"].astype(dtype)
    h = st["den"].astype(dtype).astype(jnp.float32)
    B, L, best = _setup(w.astype(jnp.float32), G, st["C"], st["H"], dtype)
    e_best = float(_err(G, B, h, best, dtype))
    lam, lo, hi, t, e_stop = 1e-5, 0.0, 1e6, 0, np.inf
    for _ in range(max_outer):
        if t >= patience or e_stop < eps:
            break
        y = _fista(G, B, best, jnp.float32(lam), L, K, dtype)
        y1 = round24(y)
        e_f, e_t = float(_err(G, B, h, y, dtype)), float(_err(G, B, h, y1,
                                                               dtype))
        if e_t < e_best:
            e_stop = (e_best - e_t) / max(e_best, 1e-30)
            best, e_best, t = y1, e_t, 0
        else:
            t += 1
        if (e_t - e_f) / max(e_t, 1e-30) > xi:
            lo = lam
        else:
            hi = lam
        lam = 0.5 * (lo + hi)
    return best.astype(dtype), e_best / float(np.sqrt(max(float(h), 1e-30)))
