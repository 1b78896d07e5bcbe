"""Fused int8 LM-head kernel (engine/lm_head.py): bit-level correctness
against the reference dequant matmul, in Pallas interpret mode on CPU.
Device-truth timing lands in PERF.md when measured on the chip."""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.lm_head import lm_head_int8
from dynamo_tpu.engine.quant import quantize_array


def _ref(x, q, scale):
    y = x.astype(jnp.float32) @ q.astype(jnp.float32)
    return y * scale.reshape(1, -1).astype(jnp.float32)


@pytest.mark.parametrize("B,D,V", [(8, 128, 512), (64, 256, 1024),
                                   (1, 128, 256), (33, 128, 768)])
def test_matches_reference(B, D, V):
    rng = np.random.default_rng(B * 1000 + V)
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((D, V)), jnp.float32)
    qa = quantize_array(w, keep_axes=(-1,))
    got = lm_head_int8(x, qa.q, qa.scale, interpret=True)
    want = _ref(x, qa.q, qa.scale)
    assert got.shape == (B, V) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_one_dim_input_squeezes():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((128,)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((128, 512)), jnp.float32)
    qa = quantize_array(w, keep_axes=(-1,))
    got = lm_head_int8(x, qa.q, qa.scale, interpret=True)
    assert got.shape == (512,)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref(x[None], qa.q, qa.scale)[0]),
        rtol=2e-2, atol=2e-2)


def test_vocab_not_divisible_raises():
    x = jnp.zeros((4, 128), jnp.bfloat16)
    q = jnp.zeros((128, 300), jnp.int8)
    s = jnp.ones((1, 300), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        lm_head_int8(x, q, s, interpret=True)


def test_logits_path_equivalence_cpu():
    """_logits with the kernel forced (interpret unavailable through the
    gate, so compare the XLA int8 path against the kernel directly on the
    same quantized head — the integration gate itself is platform-only)."""
    from dynamo_tpu.engine.models.llama import _lm_head_kernel_ok

    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((128, 512)), jnp.float32)
    qa = quantize_array(w, keep_axes=(-1,))
    x = jnp.asarray(rng.standard_normal((16, 128)), jnp.bfloat16)
    from dynamo_tpu.engine.quant import mm
    xla = mm(x, qa).astype(jnp.float32)
    ker = lm_head_int8(x, qa.q, qa.scale, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(xla),
                               rtol=2e-2, atol=2e-2)
    # CPU gate: never active off-TPU
    assert _lm_head_kernel_ok(qa) is False


def test_tp_mesh_disables_pallas_head():
    """Under tensor parallelism the vocab axis is mesh-sharded and the
    Pallas head has no GSPMD partitioning rule — the engine must clear
    the flag (review finding: the kernel would have all-gathered the
    full 70B head every step, or failed to lower)."""
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh

    mcfg = ModelConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       max_position_embeddings=128)
    ecfg = EngineConfig(max_model_len=64, kv_block_size=8, num_kv_blocks=16,
                        max_num_seqs=2, prefill_buckets=[32, 64])
    tp = EngineCore(mcfg, ecfg, attn_impl="xla", param_dtype=jnp.float32,
                    mesh=make_mesh(dp=1, tp=2))
    assert tp.model_cfg.lm_head_pallas is False
    assert tp.statics.cfg.lm_head_pallas is False
    dp = EngineCore(mcfg, ecfg, attn_impl="xla", param_dtype=jnp.float32,
                    mesh=make_mesh(dp=2, tp=1))
    assert dp.model_cfg.lm_head_pallas is True
