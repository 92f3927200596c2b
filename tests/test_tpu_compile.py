"""Every Pallas kernel of the main path compiles for a TPU v5e at real widths.

The chip is described, not attached: ``topologies.get_topology_desc``
builds a v5e:2x2 target and the TPU compiler (Mosaic for the kernels)
runs here, refusing what the chip would refuse — a block that breaks the
(8, 128) tiling rule, a lane-strided access, too much VMEM.  Interpret
mode (tests/test_kernels.py, tests/test_paged_attention.py) checks the
values; this file checks that the chip accepts the kernels at all.

Widths are opt125m-proxy's (d_model 768, d_ff 3072, head_dim 64), plus
the per-head paged decode kernel at head_dim 128 with 8 kv heads
(internlm2-20b's GQA shape; ``ops.use_decode_kernel`` takes it from
head_dim 128 up).  Below that, ``ops.use_decode_lanes`` routes head_dim
64 with 12 kv heads (768 lanes a pool row) to the lane-dense kernel,
compiled here at the batch cell's serving shapes, alone and inside the
whole decode step.  Each compiled program must contain the kernel as a
``tpu_custom_call``, and the kernels the benchmark's trace reduction
finds by name (``bench/lib/trace.KERNELS``) must keep that name.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fista_step, flash_attention, paged_attention, spmm24

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _named(text: str, kernel: str) -> bool:
    """The kernel's custom call is the HLO instruction ``%<kernel>.N``."""
    return re.search(rf"%{kernel}(?:\.\d+)? = .*custom-call\(", text) \
        is not None


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,n", [(768, 768), (768, 3072)])
def test_fista_prox_step(one_chip, m, n):
    text = _compile_text(
        lambda y, G, B: fista_step.fista_prox_step(y, G, B, 0.1, 0.01),
        _sds(one_chip, (m, n), F32), _sds(one_chip, (n, n), F32),
        _sds(one_chip, (m, n), F32))
    assert "tpu_custom_call" in text


def test_flash_attention(one_chip):
    q = _sds(one_chip, (1, 12, 2048, 64), BF16)
    text = _compile_text(
        lambda q, k, v: flash_attention.flash_attention(
            q, k, v, causal=True, bq=512, bk=512), q, q, q)
    assert "tpu_custom_call" in text
    assert _named(text, "flash_attention")


@pytest.mark.parametrize("rows", [4, 256])   # decode slots, a prefill chunk
@pytest.mark.parametrize("m,n", [(3072, 768), (768, 3072)])
def test_spmm24(one_chip, rows, m, n):
    text = _compile_text(
        lambda x, v, mt: spmm24.spmm24(x, v, mt, n),
        _sds(one_chip, (rows, n), BF16), _sds(one_chip, (m, n // 2), BF16),
        _sds(one_chip, (m, n // 4), jnp.uint8))
    assert "tpu_custom_call" in text
    assert _named(text, "spmm24")


def test_fused_mlp24_gelu(one_chip):
    d, f = 768, 3072
    text = _compile_text(
        lambda x, w1v, w1m, b1, w2v, w2m, b2: paged_attention.fused_mlp24(
            x, w1v, w1m, b1, None, None, w2v, w2m, b2, act="gelu"),
        _sds(one_chip, (4, d), BF16), _sds(one_chip, (f, d // 2), BF16),
        _sds(one_chip, (f, d // 4), jnp.uint8), _sds(one_chip, (f,), BF16),
        _sds(one_chip, (d, f // 2), BF16), _sds(one_chip, (d, f // 4), jnp.uint8),
        _sds(one_chip, (d,), BF16))
    assert "tpu_custom_call" in text
    assert _named(text, "fused_mlp24")


def test_paged_decode_attn_gqa_hd128(one_chip):
    slots, nq, nkv, hd, bs, blocks, cols = 4, 48, 8, 128, 16, 64, 8
    pool = _sds(one_chip, (blocks * bs, nkv, hd), BF16)
    text = _compile_text(
        lambda q, k, v, t, p, a: paged_attention.paged_decode_attn(
            q, k, v, t, p, a, block_size=bs),
        _sds(one_chip, (slots, nq, hd), BF16), pool, pool,
        _sds(one_chip, (slots, cols), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32), _sds(one_chip, (slots,), jnp.bool_))
    assert "tpu_custom_call" in text
    assert _named(text, "paged_decode_attn")


@pytest.mark.parametrize("nq", [12, 24])
def test_paged_decode_attn_lanes_hd64(one_chip, nq):
    """The batch cell's shapes: 64 slots, 12 kv heads of 64, blocks of
    16, 96 table columns, 6,145 pool blocks of (16, 768) rows; and GQA
    with two query heads per kv head."""
    slots, nkv, hd, bs, blocks, cols = 64, 12, 64, 16, 6145, 96
    pool = _sds(one_chip, (blocks * bs, nkv * hd), BF16)
    text = _compile_text(
        lambda q, k, v, t, p, a: paged_attention.paged_decode_attn_lanes(
            q, k, v, t, p, a, block_size=bs),
        _sds(one_chip, (slots, nq, hd), BF16), pool, pool,
        _sds(one_chip, (slots, cols), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32), _sds(one_chip, (slots,), jnp.bool_))
    assert "tpu_custom_call" in text
    assert _named(text, "paged_decode_attn")


def test_decode_step_reads_the_pool_in_place(one_chip, monkeypatch):
    """opt125m-proxy's whole decode step, routed as on a TPU: the pool's
    (nkv*hd) rows reach the kernel as they are stored, so no op of the
    step holds a per-head (…, 12, 64) view of the pool."""
    from repro.configs import opt125m_proxy
    from repro.kernels import ops
    from repro.models import transformer
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = opt125m_proxy.config().replace(tie_embeddings=True)
    slots, cols, bs = 64, 96, 16
    place = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    params = place(jax.eval_shape(
        lambda: transformer.init(cfg, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(
        lambda: transformer.init_paged_caches(cfg, 6145, bs)))
    text = _compile_text(
        lambda p, c, t, tok, pos, a: transformer.paged_serve_step(
            cfg, p, c, t, tok, pos, a, bs, impl="fused"),
        params, pool, _sds(one_chip, (slots, cols), jnp.int32),
        _sds(one_chip, (slots, 1), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32), _sds(one_chip, (slots,), jnp.bool_))
    assert _named(text, "paged_decode_attn")
    assert not re.search(r"bf16\[(?:\d+,)?98320,12,64\]", text)
