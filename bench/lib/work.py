"""Operations and bytes of the benchmark's work, from logical shapes only.

They count what the algorithm needs, whatever implementation runs it,
so a later change to a kernel cannot change its own yardstick.  bf16
weights and activations take 2 bytes; a 2:4-packed weight takes 1.25
(half the values, plus one metadata byte per four inputs).
"""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2
PACKED24 = 1.25


def layer_matrices(dm: Dict[str, Any]) -> Dict[str, tuple]:
    """(in, out) of every linear operator of one layer."""
    d, f = dm["d"], dm["d_ff"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    out = {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
           "attn/wo": (q, d)}
    if dm["act"] == "silu":
        out.update({"mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)})
    else:
        out.update({"mlp/fc1": (d, f), "mlp/fc2": (f, d)})
    return out


def layer_weights(dm: Dict[str, Any]) -> int:
    return sum(i * o for i, o in layer_matrices(dm).values())


def kv_bytes_per_token(dm: Dict[str, Any]) -> int:
    """K and V of one position across all layers."""
    return 2 * dm["layers"] * dm["kv_heads"] * dm["head_dim"] * BF16


def decode_step(dm: Dict[str, Any], tokens: float, context: float,
                packed: bool) -> Dict[str, float]:
    """One decode step for ``tokens`` active slots whose contexts sum to
    ``context`` positions: FLOPs, and bytes of weights (as stored) plus
    the K/V of those contexts."""
    lw = layer_weights(dm)
    head = dm["d"] * dm["vocab"]
    flops = 2.0 * tokens * (dm["layers"] * lw * (0.5 if packed else 1.0)
                            + head)
    flops += 4.0 * dm["layers"] * dm["heads"] * dm["head_dim"] * context
    wbytes = dm["layers"] * lw * (PACKED24 if packed else BF16) + head * BF16
    return {"flops": flops,
            "bytes": wbytes + context * kv_bytes_per_token(dm)}


def spmm24(dm: Dict[str, Any], rows: int) -> Dict[str, float]:
    """The attention projections of one decode step, all layers, as
    ``spmm24`` computes them over ``rows`` slot rows: the nonzero
    multiply-adds, and packed weights plus each call's input and output
    rows."""
    flops, nbytes = 0.0, 0.0
    for key, (i, o) in layer_matrices(dm).items():
        if key.startswith("attn/"):
            flops += 2.0 * rows * i * o * 0.5
            nbytes += i * o * PACKED24 + rows * (i + o) * BF16
    return {"flops": dm["layers"] * flops, "bytes": dm["layers"] * nbytes}


def fused_mlp24(dm: Dict[str, Any], rows: int) -> Dict[str, float]:
    """The MLP of one decode step, all layers, as ``fused_mlp24``
    computes it over ``rows`` slot rows: the nonzero multiply-adds, and
    packed weights plus the input and output rows (the hidden activation
    never leaves the kernel)."""
    flops, nbytes = 0.0, 0.0
    for key, (i, o) in layer_matrices(dm).items():
        if key.startswith("mlp/"):
            flops += 2.0 * rows * i * o * 0.5
            nbytes += i * o * PACKED24
    nbytes += 2 * rows * dm["d"] * BF16
    return {"flops": dm["layers"] * flops, "bytes": dm["layers"] * nbytes}


def decode_attention(dm: Dict[str, Any], tokens: float, context: float
                     ) -> Dict[str, float]:
    """Paged decode attention of one step, all layers: the K/V of the
    active contexts, and q/out rows."""
    hd, L = dm["head_dim"], dm["layers"]
    flops = 4.0 * L * dm["heads"] * hd * context
    nbytes = context * kv_bytes_per_token(dm) \
        + 2.0 * L * tokens * dm["heads"] * hd * BF16
    return {"flops": flops, "bytes": nbytes}


def unit_forward(dm: Dict[str, Any], seqs: int, seq_len: int) -> float:
    """FLOPs of one decoder layer over ``seqs`` sequences; causal
    attention counts only the scores and sums it needs (half of T x T)."""
    tokens = seqs * seq_len
    return 2.0 * tokens * layer_weights(dm) \
        + 2.0 * seqs * seq_len * seq_len * dm["heads"] * dm["head_dim"]


def prune(dm: Dict[str, Any], seqs: int, seq_len: int, reports) -> float:
    """FLOPs of one whole prune, every unit with intra-unit correction
    (``reports``: one per operator solved): per unit the relay's
    forward, the dense capture forward and one pruned-path forward per
    operator group, three Grams and the target W X per operator, and
    per solve one (m, n) x (n, n) product per FISTA iteration and two
    per outer iteration (the errors), plus B = W C and 64 power steps."""
    tokens = seqs * seq_len
    groups = 4      # q/k/v, o, the MLP's input side, the MLP's output side
    flops = (2 + groups) * unit_forward(dm, seqs, seq_len)
    for i, o in layer_matrices(dm).values():
        flops += 3 * 2.0 * tokens * i * i + 2.0 * tokens * i * o
    flops *= dm["layers"]
    for r in reports:
        m, n = r["shape"]
        flops += 2.0 * m * n * n * (r["fista_iters"] + 2 * r["outer_iters"]
                                    + 1) + 64 * 2.0 * n * n
    return flops
