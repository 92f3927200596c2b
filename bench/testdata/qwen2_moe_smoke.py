"""Test fixture: a configuration's reference that brings its own layer layout.

Qwen1.5-MoE-A2.7B at the program's smoke sizes (``qwen2-moe-smoke.json``
beside this file): each layer has attention with a bias on q, k and v,
then a router over ``num_experts`` routed SwiGLU experts of which each
token takes ``num_experts_per_tok``, and one shared SwiGLU expert
scaled by a sigmoid gate.  Like every reference, it declares:

* ``layout``: the seeded tree as the program stores it, experts stacked
  (layers, held, in, out), the router in float32;
* ``program_sizes``: the program's config attributes to check, those of
  the nested ``moe`` config among them;
* ``dims()["layer_kinds"]``: the leaves of ``layout`` that the work
  counts sum over, each with how many of its matrices a token passes:
  every expert is held here, so a token passes
  ``top_k * held / experts = top_k`` of them.

It has no forward pass: it tests the harness.  The plain MoE reference
belongs with a configuration that runs on the chip.
"""
from __future__ import annotations

from typing import Any, Dict


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh, hd = cfg["num_key_value_heads"], d // heads
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    held = E
    experts = k * held / E
    operators = {f"layers/{p}": 1 for p in (
        "attn/wq", "attn/wk", "attn/wv", "attn/wo", "moe/router",
        "moe/shared/gate", "moe/shared/up", "moe/shared/down",
        "moe/shared_gate")}
    operators.update({f"layers/moe/{p}": experts
                      for p in ("w_gate", "w_up", "w_down")})
    L = cfg["num_hidden_layers"]
    return {"d": d, "heads": heads, "kv_heads": kvh, "head_dim": hd,
            "vocab": cfg["vocab_size"], "layers": L, "experts": E,
            "held": held, "top_k": k,
            "expert_ff": cfg["moe_intermediate_size"],
            "shared_ff": cfg["shared_expert_intermediate_size"],
            "norm_topk": cfg["norm_topk_prob"], "theta": cfg["rope_theta"],
            "eps": cfg["rms_norm_eps"], "tie": cfg["tie_word_embeddings"],
            "layer_kinds": [{"count": L, "operators": operators,
                             "attention": {"heads": heads, "head_dim": hd,
                                           "kv_elems_per_token": 2 * kvh * hd,
                                           "window": None}}]}


def layout(dm: Dict[str, Any], bias: bool) -> Dict[str, tuple]:
    """Qwen's q/k/v carry a bias whatever ``bias`` says."""
    L, d, V = dm["layers"], dm["d"], dm["vocab"]
    E, H = dm["experts"], dm["held"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    fe, fs = dm["expert_ff"], dm["shared_ff"]
    out = {"embed": ((V, d), "embed"), "head": ((d, V), "dense_matrix"),
           "final_norm/scale": ((d,), "scale"),
           "layers/ln1/scale": ((L, d), "scale"),
           "layers/ln2/scale": ((L, d), "scale"),
           "layers/attn/wq": ((L, d, q), "matrix"),
           "layers/attn/wk": ((L, d, kv), "matrix"),
           "layers/attn/wv": ((L, d, kv), "matrix"),
           "layers/attn/wo": ((L, q, d), "matrix"),
           "layers/attn/bq": ((L, q), "vector"),
           "layers/attn/bk": ((L, kv), "vector"),
           "layers/attn/bv": ((L, kv), "vector"),
           "layers/moe/router": ((L, d, E), "dense_matrix", "float32"),
           "layers/moe/w_gate": ((L, H, d, fe), "matrix"),
           "layers/moe/w_up": ((L, H, d, fe), "matrix"),
           "layers/moe/w_down": ((L, H, fe, d), "matrix"),
           "layers/moe/shared/gate": ((L, d, fs), "matrix"),
           "layers/moe/shared/up": ((L, d, fs), "matrix"),
           "layers/moe/shared/down": ((L, fs, d), "matrix"),
           "layers/moe/shared_gate": ((L, d, 1), "dense_matrix")}
    return out


def program_sizes(dm: Dict[str, Any], bias: bool) -> Dict[str, Any]:
    return {"num_layers": dm["layers"], "d_model": dm["d"],
            "num_heads": dm["heads"], "num_kv_heads": dm["kv_heads"],
            "resolved_head_dim": dm["head_dim"], "vocab": dm["vocab"],
            "norm": "rmsnorm", "act": "silu", "rope_theta": dm["theta"],
            "tie_embeddings": dm["tie"], "qkv_bias": True, "window": None,
            "moe.num_experts": dm["experts"], "moe.top_k": dm["top_k"],
            "moe.expert_ff": dm["expert_ff"], "moe.num_shared": 1,
            "moe.shared_ff": dm["shared_ff"],
            "moe.norm_topk": dm["norm_topk"]}
