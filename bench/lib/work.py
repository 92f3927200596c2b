"""Operations and bytes of the benchmark's work, from logical shapes only.

They count what the algorithm needs, whatever implementation runs it,
so a later change to a kernel cannot change its own yardstick.  A
weight takes its dtype's bytes (bf16 2, float32 4) and activations 2;
in a packed cell a 2:4 ``matrix`` weight takes 1.25 (half the values in
bf16, plus one metadata byte per four inputs) and half the FLOPs, and
every other weight (a ``dense_matrix``: head, router, gate) stays as
stored.

Every count sums over the model's layer kinds.  A configuration's
reference may give them in its ``dims`` as ``dm["layer_kinds"]``, a
list of

    {"count": how many layers are of this kind,
     "operators": {leaf path: per_token},
     "attention": {"heads", "head_dim", "kv_elems_per_token", "window"}
                  or None for a layer without attention}

Each operator is a leaf of the reference's ``layout``, which
``harness.ref_dims`` puts into ``dm["leaves"]``: a ``matrix`` or
``dense_matrix`` stacked over layers, ``(layers, *held, in, out)``.
Its in, out, role and dtype come from there, and ``stored``, the
(in, out) matrices one layer holds under it, is the product of the
``held`` axes (the experts held; 1 for a plain operator).
``per_token`` is how many of them one token passes through
(``top_k * held / experts`` for routed experts; 1 for a plain
operator): weight bytes come from ``stored``, FLOPs per token from
``per_token``.  ``kv_elems_per_token`` is the K and V elements one
position keeps in one layer, and ``window`` is None for full attention.
A ``matrix`` under ``attn/`` is one ``spmm24`` computes, one under
``mlp/`` one ``fused_mlp24`` computes.  Without ``layer_kinds`` the one
kind is the dense decoder of ``dm``'s sizes, a count of sizes alone.

A windowed kind reads at most ``window`` positions of each slot, never
its full context.  That count comes from the run record's counter
``WINDOWED_CONTEXT``: over the window's decode steps, each active slot's
context capped at the window, summed.  Where a windowed kind exists and
the count is not given, the counts of a decode step are None.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional

BF16 = 2
PACKED24 = 1.25
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}

#: counter of ``run["stats"]`` that windowed layers read (see above)
WINDOWED_CONTEXT = "windowed_context_tokens"


class Op(NamedTuple):
    """One operator of a layer: (in, out) matrices stored and passed."""
    i: int
    o: int
    stored: int
    per_token: float
    role: str
    dtype: str


def _dense_kind(dm: Dict[str, Any]) -> Dict[str, Any]:
    """The one kind of a dense decoder of ``dm``'s sizes."""
    d, f, hd = dm["d"], dm["d_ff"], dm["head_dim"]
    q, kv = dm["heads"] * hd, dm["kv_heads"] * hd
    mats = {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d)}
    if dm["act"] == "silu":
        mats.update({"mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)})
    else:
        mats.update({"mlp/fc1": (d, f), "mlp/fc2": (f, d)})
    return {"count": dm["layers"],
            "operators": {"layers/" + k: Op(i, o, 1, 1, "matrix", "bfloat16")
                          for k, (i, o) in mats.items()},
            "attention": {"heads": dm["heads"], "head_dim": hd,
                          "kv_elems_per_token": 2 * kv, "window": None}}


def _op(leaves: Dict[str, tuple], path: str, per_token: float) -> Op:
    if path not in leaves:
        raise ValueError(f"operator {path!r} is no leaf of the layout")
    shape, role, dtype = leaves[path]
    if role not in ("matrix", "dense_matrix") or len(shape) < 3:
        raise ValueError(f"operator {path!r} is a {role} of shape {shape}, "
                         f"not a matrix stacked over layers")
    return Op(shape[-2], shape[-1], math.prod(shape[1:-2]), per_token, role,
              dtype)


def layer_kinds(dm: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The layer kinds, each operator an ``Op``; without declared
    kinds, the dense decoder as the one kind."""
    if "layer_kinds" not in dm:
        return [_dense_kind(dm)]
    leaves = {p: (s, r, t) for p, s, r, t in dm["leaves"]}
    kinds = [dict(k, operators={p: _op(leaves, p, t)
                                for p, t in k["operators"].items()})
             for k in dm["layer_kinds"]]
    if sum(k["count"] for k in kinds) != dm["layers"]:
        raise ValueError(f"layer kinds count {sum(k['count'] for k in kinds)}"
                         f" layers, the model {dm['layers']}")
    windows = {k["attention"]["window"] for k in kinds if k["attention"]
               and k["attention"]["window"] is not None}
    if len(windows) > 1:
        raise ValueError(f"one windowed count serves one window, not "
                         f"{sorted(windows)}")
    return kinds


def _in(path: str, group: str) -> bool:
    return group in path.split("/")[:-1]


def _weight_bytes(op: Op, packed: bool) -> float:
    return PACKED24 if packed and op.role == "matrix" else ITEMSIZE[op.dtype]


def _per_token(kind, packed: bool = False) -> float:
    """Multiply-adds of one token through the kind's operators; a packed
    2:4 matrix computes half of them."""
    return sum(op.i * op.o * op.per_token
               * (0.5 if packed and op.role == "matrix" else 1.0)
               for op in kind["operators"].values())


def _blind(kinds, windowed: Optional[float]) -> bool:
    """A windowed kind, and no windowed count to read it by."""
    return windowed is None and any(
        k["attention"] and k["attention"]["window"] is not None for k in kinds)


def layer_weights(dm: Dict[str, Any]) -> float:
    """Weights of one layer as stored; where layers differ, the mean."""
    return sum(k["count"] * op.i * op.o * op.stored for k in layer_kinds(dm)
               for op in k["operators"].values()) / dm["layers"]


def per_step(stats: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """A run record's decode counters per step: active slots
    (``tokens``), their contexts' positions summed (``context``), and
    the same capped at the window (``windowed``; None if not counted)."""
    n = stats["steps"]
    w = stats.get(WINDOWED_CONTEXT)
    return {"tokens": stats["active_slot_steps"] / n,
            "context": stats["context_tokens"] / n,
            "windowed": None if w is None else w / n}


def decode_step(dm: Dict[str, Any], tokens: float, context: float,
                packed: bool, windowed: Optional[float] = None
                ) -> Optional[Dict[str, float]]:
    """One decode step for ``tokens`` active slots whose contexts sum to
    ``context`` positions (``windowed``: capped at the window): FLOPs,
    and bytes of weights (as stored) plus the K/V those layers read."""
    kinds = layer_kinds(dm)
    if _blind(kinds, windowed):
        return None
    head = dm["d"] * dm["vocab"]
    per_tok = sum(k["count"] * _per_token(k, packed) for k in kinds)
    flops = 2.0 * tokens * (per_tok + head)
    att = decode_attention(dm, tokens, context, windowed)
    flops += att["flops"]
    wbytes = sum(k["count"] * op.i * op.o * op.stored
                 * _weight_bytes(op, packed)
                 for k in kinds for op in k["operators"].values())
    return {"flops": flops, "bytes": wbytes + head * BF16 + att["kv_bytes"]}


def spmm24(dm: Dict[str, Any], rows: int) -> Dict[str, float]:
    """The attention projections of one decode step, all layers, as
    ``spmm24`` computes them over ``rows`` slot rows: the nonzero
    multiply-adds, and packed weights plus each call's input and output
    rows."""
    total_f, total_b = 0.0, 0.0
    for kind in layer_kinds(dm):
        flops, nbytes = 0.0, 0.0
        for path, op in kind["operators"].items():
            if _in(path, "attn") and op.role == "matrix":
                i, o, t = op.i, op.o, op.per_token
                flops += 2.0 * rows * i * o * 0.5 * t
                nbytes += i * o * op.stored * PACKED24 \
                    + rows * t * (i + o) * BF16
        total_f += kind["count"] * flops
        total_b += kind["count"] * nbytes
    return {"flops": total_f, "bytes": total_b}


def fused_mlp24(dm: Dict[str, Any], rows: int) -> Dict[str, float]:
    """The MLP of one decode step, all layers, as ``fused_mlp24``
    computes it over ``rows`` slot rows: the nonzero multiply-adds, and
    packed weights plus the input and output rows (the hidden activation
    never leaves the kernel)."""
    total_f, total_b = 0.0, 0.0
    for kind in layer_kinds(dm):
        mlp = [op for path, op in kind["operators"].items()
               if _in(path, "mlp") and op.role == "matrix"]
        if not mlp:
            continue
        flops, nbytes = 0.0, 0.0
        for op in mlp:
            flops += 2.0 * rows * op.i * op.o * 0.5 * op.per_token
            nbytes += op.i * op.o * op.stored * PACKED24
        nbytes += 2 * rows * dm["d"] * BF16
        total_f += kind["count"] * flops
        total_b += kind["count"] * nbytes
    return {"flops": total_f, "bytes": total_b}


def decode_attention(dm: Dict[str, Any], tokens: float, context: float,
                     windowed: Optional[float] = None
                     ) -> Optional[Dict[str, float]]:
    """Paged decode attention of one step, all layers: the K/V of the
    active contexts (``kv_bytes``; a windowed layer's capped at its
    window), and q/out rows."""
    kinds = layer_kinds(dm)
    if _blind(kinds, windowed):
        return None
    flops, kv, rows = 0.0, 0.0, 0.0
    for k in kinds:
        a, n = k["attention"], k["count"]
        if not a:
            continue
        ctx = context if a["window"] is None else windowed
        flops += 4.0 * n * a["heads"] * a["head_dim"] * ctx
        kv += ctx * (n * a["kv_elems_per_token"] * BF16)
        rows += 2.0 * n * tokens * a["heads"] * a["head_dim"] * BF16
    return {"flops": flops, "bytes": kv + rows, "kv_bytes": kv}


def _unit_forward(kind, seqs: int, seq_len: int) -> float:
    a = kind["attention"]
    att = 0.0
    if a and (a["window"] is None or seq_len <= a["window"]):
        att = 2.0 * seqs * seq_len * seq_len * a["heads"] * a["head_dim"]
    elif a:                    # each query sees at most the window
        w = a["window"]
        att = 2.0 * seqs * (2 * w * seq_len - w * w) * a["heads"] \
            * a["head_dim"]
    return 2.0 * (seqs * seq_len) * _per_token(kind) + att


def unit_forward(dm: Dict[str, Any], seqs: int, seq_len: int) -> float:
    """FLOPs of one decoder layer over ``seqs`` sequences (where layers
    differ, the mean); causal attention counts only the scores and sums
    it needs (half of T x T, or of the band a window leaves)."""
    return sum(k["count"] * _unit_forward(k, seqs, seq_len)
               for k in layer_kinds(dm)) / dm["layers"]


def prune(dm: Dict[str, Any], seqs: int, seq_len: int, reports) -> float:
    """FLOPs of one whole prune, every unit with intra-unit correction
    (``reports``: one per operator solved): per unit the relay's
    forward, the dense capture forward and one pruned-path forward per
    operator group, three Grams and the target W X per operator (over
    the tokens routed to it), and per solve one (m, n) x (n, n) product
    per FISTA iteration and two per outer iteration (the errors), plus
    B = W C and 64 power steps."""
    tokens = seqs * seq_len
    groups = 4      # q/k/v, o, the MLP's input side, the MLP's output side
    flops = 0.0
    for kind in layer_kinds(dm):
        f = (2 + groups) * _unit_forward(kind, seqs, seq_len)
        for op in kind["operators"].values():
            f += (3 * 2.0 * tokens * op.i * op.i
                  + 2.0 * tokens * op.i * op.o) * op.per_token
        flops += f * kind["count"]
    for r in reports:
        m, n = r["shape"]
        flops += 2.0 * m * n * n * (r["fista_iters"] + 2 * r["outer_iters"]
                                    + 1) + 64 * 2.0 * n * n
    return flops
