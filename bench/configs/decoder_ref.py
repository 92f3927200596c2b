"""Plain float32 reference of the decoder-only transformers in this benchmark.

Written from the published layer equations, with no kernel, cache or
batching and nothing imported from the system under test.  A
configuration file names this module under ``"reference"`` and gives
the sizes it reads (``hidden_size``, ``num_attention_heads`` ...).

One pre-norm decoder layer, for input x (tokens, d):

    h  = norm1(x);  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    q, k rotated by RoPE;  a = softmax(q k^T / sqrt(hd) + causal) v   (GQA)
    x  = x + a Wo
    h  = norm2(x)
    x  = x + act(h Wfc1 + b1) Wfc2 + b2               (GELU MLP, OPT)
       | x + (silu(h Wgate) * (h Wup)) Wdown            (SwiGLU, InternLM2)

then logits = norm_f(x) Whead, or norm_f(x) Wembed^T with a tied head.
Departures from the published models, made because the system under
test computes them so (a configuration's ``assumed``): RoPE rotates
interleaved pairs (x0, x1), (x2, x3) ... which equals the rotate-half
form up to a fixed permutation of the q/k head dimensions; the OPT
proxy uses RoPE, the tanh GELU and no bias on the attention output
where OPT-125M has learned positions, ReLU and that bias.

Weights arrive as the benchmark's own seeded tree (``bench/lib/weights``):
matrices are stored (in, out), stacked over layers.  Every matmul runs
in float32 at ``Precision.HIGHEST``.  With ``q`` set, every matmul takes
both operands rounded to float8 e4m3 (one scale per row of activations
and per column of weights): the benchmark's control, the reference one
precision below the bf16 the configurations state.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024     # attention rows per block of scores
PAD = 512              # sequences and row sets are padded to multiples


#: activation names as configuration files give them -> this reference's
ACTS = {"gelu_pytorch_tanh": "gelu", "silu": "silu"}


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes this reference needs, read from a configuration file.
    Where the file's ``assumed`` gives what the system under test
    computes in place of the published value, that is what is read."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    run = dict(cfg, **{k: v for k, v in cfg.get("assumed", {}).items()
                       if k in ("activation_function", "rope_theta")})
    return {
        "d": d, "heads": heads,
        "kv_heads": cfg.get("num_key_value_heads", heads),
        "head_dim": cfg.get("head_dim", d // heads),
        "d_ff": cfg.get("intermediate_size", cfg.get("ffn_dim")),
        "vocab": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "norm": "layernorm" if cfg.get("do_layer_norm_before") else "rmsnorm",
        "eps": cfg.get("rms_norm_eps", cfg.get("layer_norm_eps", 1e-5)),
        "act": ACTS[run.get("hidden_act", run.get("activation_function"))],
        "theta": float(run.get("rope_theta", 10000.0)),
        "tie": bool(cfg.get("tie_word_embeddings", False)),
    }


def layout(dm: Dict[str, Any], bias: bool) -> Dict[str, tuple]:
    """The seeded tree this reference reads: leaf path -> (shape, role),
    the roles as ``bench/lib/weights`` gives them; ``bias`` adds q/k/v
    biases."""
    L, d, f, V = dm["layers"], dm["d"], dm["d_ff"], dm["vocab"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    out = {"embed": ((V, d), "embed")}
    if not dm["tie"]:
        out["head"] = ((d, V), "dense_matrix")
    norms = {"scale": "scale", "bias": "vector"}
    for n in ("scale", "bias") if dm["norm"] == "layernorm" else ("scale",):
        out[f"layers/ln1/{n}"] = out[f"layers/ln2/{n}"] = ((L, d), norms[n])
        out[f"final_norm/{n}"] = ((d,), norms[n])
    out.update({"layers/attn/wq": ((L, d, q), "matrix"),
                "layers/attn/wk": ((L, d, kv), "matrix"),
                "layers/attn/wv": ((L, d, kv), "matrix"),
                "layers/attn/wo": ((L, q, d), "matrix")})
    if bias:
        out.update({"layers/attn/bq": ((L, q), "vector"),
                    "layers/attn/bk": ((L, kv), "vector"),
                    "layers/attn/bv": ((L, kv), "vector")})
    if dm["act"] == "silu":
        out.update({"layers/mlp/gate": ((L, d, f), "matrix"),
                    "layers/mlp/up": ((L, d, f), "matrix"),
                    "layers/mlp/down": ((L, f, d), "matrix")})
    else:
        out.update({"layers/mlp/fc1": ((L, d, f), "matrix"),
                    "layers/mlp/b1": ((L, f), "vector"),
                    "layers/mlp/fc2": ((L, f, d), "matrix"),
                    "layers/mlp/b2": ((L, d), "vector")})
    return out


def program_sizes(dm: Dict[str, Any], bias: bool) -> Dict[str, Any]:
    """The program's config attributes (dotted) that must equal these
    sizes."""
    return {"num_layers": dm["layers"], "d_model": dm["d"],
            "num_heads": dm["heads"], "num_kv_heads": dm["kv_heads"],
            "resolved_head_dim": dm["head_dim"], "d_ff": dm["d_ff"],
            "vocab": dm["vocab"], "norm": dm["norm"], "act": dm["act"],
            "rope_theta": dm["theta"], "tie_embeddings": dm["tie"],
            "qkv_bias": bool(bias)}


def fp8(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 with one absmax scale per slice along ``axis``
    (the reduced axis of the matmul), back to float32."""
    a = a.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, q: bool):
    if q:       # the control: both matmul operands in fp8
        return jnp.matmul(fp8(x, -1), fp8(w, -2), precision=HIGHEST)
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _norm(dm, p, x):
    if dm["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + dm["eps"]) * p["scale"].astype(
            jnp.float32) + p["bias"].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + dm["eps"]) * p["scale"].astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, H, hd), pos (T,): rotate interleaved pairs by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _bias(p, name):
    b = p.get(name)
    return 0.0 if b is None else b.astype(jnp.float32)


def layer(dm, p, x, pos, q: bool = False, cap: Optional[Dict] = None):
    """One decoder layer on one sequence x (T, d) float32.  ``cap``, when
    given, receives the input of every linear operator under the weight's
    path (``attn/wq`` ...)."""
    def lin(name, h, w, b=0.0):
        if cap is not None:
            cap[name] = h
        return _mm(h, w, q) + b

    a_p, hd = p["attn"], dm["head_dim"]
    T = x.shape[0]
    h = _norm(dm, p["ln1"], x)
    qh = lin("attn/wq", h, a_p["wq"], _bias(a_p, "bq")).reshape(T, dm["heads"], hd)
    kh = lin("attn/wk", h, a_p["wk"], _bias(a_p, "bk")).reshape(T, dm["kv_heads"], hd)
    vh = lin("attn/wv", h, a_p["wv"], _bias(a_p, "bv")).reshape(T, dm["kv_heads"], hd)
    qh, kh = _rope(qh, pos, dm["theta"]), _rope(kh, pos, dm["theta"])
    g = dm["heads"] // dm["kv_heads"]
    kh, vh = jnp.repeat(kh, g, axis=1), jnp.repeat(vh, g, axis=1)
    outs = []
    for r in range(0, T, QUERY_BLOCK):      # queries in blocks: bounded scores
        s = jnp.einsum("qhd,khd->hqk", qh[r:r + QUERY_BLOCK], kh,
                       precision=HIGHEST) / np.sqrt(hd)
        causal = pos[r:r + QUERY_BLOCK, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vh,
                               precision=HIGHEST))
    o = jnp.concatenate(outs).reshape(T, dm["heads"] * hd)
    x = x + lin("attn/wo", o, a_p["wo"], _bias(a_p, "bo"))
    h = _norm(dm, p["ln2"], x)
    m = p["mlp"]
    if "gate" in m:
        u = jax.nn.silu(lin("mlp/gate", h, m["gate"])) * lin("mlp/up", h, m["up"])
        return x + lin("mlp/down", u, m["down"])
    u = jax.nn.gelu(lin("mlp/fc1", h, m["fc1"], _bias(m, "b1")), approximate=True)
    return x + lin("mlp/fc2", u, m["fc2"], _bias(m, "b2"))


def layer_params(params, i):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(dm, params, x, q: bool = False):
    w = params["embed"].T if dm["tie"] else params["head"]
    return _mm(_norm(dm, params["final_norm"], x), w, q)


def make_forward(dm):
    """Jitted pieces of a whole-sequence forward: (layer_fn, head_fn)."""
    layer_fn = jax.jit(lambda p, x, pos, q: layer(dm, p, x, pos, q),
                       static_argnums=3)
    head_fn = jax.jit(lambda params, x, q: head(dm, params, x, q),
                      static_argnums=2)
    return layer_fn, head_fn


def logits(dm, params, tokens: np.ndarray, rows: np.ndarray,
           q: bool = False, fns=None) -> jnp.ndarray:
    """Logits (len(rows), vocab) at positions ``rows`` of one sequence,
    computed layer by layer so that only one layer's float32 weights
    exist at a time."""
    layer_fn, head_fn = fns or make_forward(dm)
    # pad the sequence (after its end, so causal rows are unchanged) and
    # the rows to fixed steps: few distinct shapes, few compiles
    T, R = len(tokens), len(rows)
    tok = np.zeros(-(-T // PAD) * PAD, np.int32)
    tok[:T] = tokens
    rws = np.zeros(-(-R // PAD) * PAD, np.int32)
    rws[:R] = rows
    pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
    x = embed(params, jnp.asarray(tok))
    for i in range(dm["layers"]):
        x = layer_fn(layer_params(params, i), x, pos, q)
    last = {k: params[k] for k in ("final_norm", "head", "embed")
            if k in params}
    return head_fn(last, x[jnp.asarray(rws)], q)[:R]
