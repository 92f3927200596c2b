"""Decode-vs-teacher-forcing consistency for the recurrent/stateful
families (the transformer family is covered in test_substrate.py).

For each arch: feed a short prompt token-by-token through serve_step and
check each step's next-token logits match the full-sequence forward at
that position — the strictest functional test of the cache/state
plumbing (ring buffers, conv windows, SSM states, cross-attention).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models.registry import load_arch


def _stepwise_logits(d, params, tokens, extras=None, cache_len=32):
    B, S = tokens.shape
    state = d.init_serve_state(params, B, cache_len, extras)
    outs = []
    for t in range(S):
        logits, state = d.serve_step(params, state, tokens[:, t:t + 1],
                                     jnp.int32(t))
        outs.append(np.asarray(logits[:, -1, :], np.float32))
    return np.stack(outs, axis=1)  # (B, S, V)


@pytest.mark.parametrize("arch", ["mamba2-780m", "recurrentgemma-9b"])
def test_recurrent_decode_matches_forward(arch):
    d = load_arch(arch, smoke=True)
    params = d.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                d.cfg.vocab, jnp.int32)
    got = _stepwise_logits(d, params, tokens)
    want = np.asarray(d.forward_logits(params, {"tokens": tokens}), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_whisper_decode_matches_forward():
    d = load_arch("whisper-base", smoke=True)
    params = d.init(jax.random.PRNGKey(0))
    batch = d.make_batch(jax.random.PRNGKey(1), 2, 10)
    tokens = batch["tokens"]
    got = _stepwise_logits(d, params, tokens, {"frames": batch["frames"]})
    want = np.asarray(d.forward_logits(params, batch), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_windowed_attention_ring_buffer():
    """mixtral's SWA ring cache: decode past the window must equal the
    windowed full forward (positions beyond the window are evicted)."""
    d = load_arch("mixtral-8x7b", smoke=True)   # window=16 in smoke config
    params = d.init(jax.random.PRNGKey(0))
    S = 24  # > window
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, S), 0,
                                d.cfg.vocab, jnp.int32)
    got = _stepwise_logits(d, params, tokens, cache_len=d.cfg.window)
    want = np.asarray(d.forward_logits(params, {"tokens": tokens}), np.float32)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_windowed_attention_wide_cache():
    """A cache *wider* than the window must still mask attention to the
    window: decode == windowed full forward.  (The non-ring decode branch
    used to skip the window cut and attend to everything <= pos.)"""
    d = load_arch("mixtral-8x7b", smoke=True)   # window=16 in smoke config
    params = d.init(jax.random.PRNGKey(0))
    S = 24  # > window
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, S), 0,
                                d.cfg.vocab, jnp.int32)
    got = _stepwise_logits(d, params, tokens, cache_len=2 * d.cfg.window)
    want = np.asarray(d.forward_logits(params, {"tokens": tokens}), np.float32)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_window_mask_helper_shared_by_decode_paths():
    """``common.decode_window_mask`` is the single source of the decode
    length + sliding-window cut.  Pin (a) its truth table against the
    two formulas it replaced (contiguous non-ring branch; paged gather
    branch), and (b) that the contiguous and paged decode paths agree
    bitwise through it on a window narrower than the cache."""
    from repro.models import common
    from repro.configs.opt125m_proxy import tiny_config

    # (a) truth table, scalar and broadcast pos, window None / narrow
    idx = jnp.arange(16, dtype=jnp.int32)
    for pos in (0, 5, 15):
        for window in (None, 4, 16):
            got = np.asarray(common.decode_window_mask(idx, jnp.int32(pos),
                                                       window))
            want = (np.arange(16) <= pos)
            if window is not None:
                want &= np.arange(16) > pos - window
            np.testing.assert_array_equal(got, want, err_msg=f"{pos},{window}")
    posb = jnp.asarray([[3], [9]], jnp.int32)
    got = np.asarray(common.decode_window_mask(idx[None, :], posb, 4))
    want = (np.arange(16)[None, :] <= np.asarray(posb)) \
        & (np.arange(16)[None, :] > np.asarray(posb) - 4)
    np.testing.assert_array_equal(got, want)

    # (b) contiguous mha_decode == paged mha_decode_paged, windowed,
    # cache wider than the window (both paths route through the helper)
    cfg = tiny_config().replace(num_layers=1, d_model=16, num_heads=2,
                                num_kv_heads=2, vocab=32, window=6)
    p = common.attn_init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    S, W, nkv, hd = 2, 16, 2, cfg.resolved_head_dim()
    x = jnp.asarray(rng.standard_normal((S, 1, cfg.d_model)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
    pos = np.asarray([9, 14], np.int32)
    # identity paging: slot b's context lives at flat slots b*W + [0, W)
    flat = {"k": ck.reshape(S * W, nkv, hd), "v": cv.reshape(S * W, nkv, hd)}
    gather = jnp.asarray(np.arange(S * W).reshape(S, W))
    paged, _ = common.mha_decode_paged(
        cfg, p, x, jnp.asarray(pos), flat,
        jnp.asarray(np.arange(S) * W + pos), gather, jnp.ones((S,), bool),
        cfg.window)
    # all S rows decode at slot b's position and row b is compared: XLA's
    # CPU dot rounds a 1-row matmul differently from an S-row one
    for b in range(S):
        contig, _ = common.mha_decode(cfg, p, x, jnp.int32(pos[b]),
                                      {"k": ck, "v": cv}, window=cfg.window)
        np.testing.assert_array_equal(np.asarray(paged[b:b + 1]),
                                      np.asarray(contig[b:b + 1]))


def test_flash_attention_matches_xla_forward():
    """attn_impl='flash' == 'xla' on the same params (S >= 128 kernel path)."""
    from repro.models.registry import model_def
    d_xla = load_arch("stablelm-1.6b", smoke=True)
    cfg = d_xla.cfg.replace(max_seq=256, attn_impl="xla")
    d_xla = model_def(cfg)
    d_fla = model_def(cfg.replace(attn_impl="flash"))
    params = d_xla.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 192), 0,
                                cfg.vocab, jnp.int32)
    a = np.asarray(d_xla.forward_logits(params, {"tokens": tokens}), np.float32)
    b = np.asarray(d_fla.forward_logits(params, {"tokens": tokens}), np.float32)
    np.testing.assert_allclose(b, a, rtol=5e-3, atol=5e-3)


def test_flash_attention_train_grads_match():
    from repro.models.registry import model_def
    base = load_arch("stablelm-1.6b", smoke=True).cfg.replace(max_seq=256)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 160), 0,
                                base.vocab, jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    grads = {}
    for impl in ("xla", "flash"):
        d = model_def(base.replace(attn_impl=impl))
        params = d.init(jax.random.PRNGKey(0))
        g = jax.grad(lambda p: d.loss(p, batch)[0])(params)
        grads[impl] = g
    ga = np.asarray(grads["xla"]["layers"]["attn"]["wq"], np.float32)
    gb = np.asarray(grads["flash"]["layers"]["attn"]["wq"], np.float32)
    np.testing.assert_allclose(gb, ga, rtol=2e-2, atol=1e-4)
