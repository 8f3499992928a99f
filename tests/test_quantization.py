"""Quantization tests (reference ``quantization/`` — quantize.py:13 convert,
observer.py PerChannelAbsMaxObserver, test/unit_test/quantization).

int8 weight-only quantization of a tiny Llama: quantized generate stays close
to the fp golden, scales are per-output-channel (incl. the fan-in-only
reduction for 3D GQA and expert kernels), and sharding specs survive.
"""

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.quantization.core import (
    QuantizationConfig,
    QuantizedLeaf,
    dequantize_params,
    quantize_params,
    quantized_apply,
)
from neuronx_distributed_tpu.trainer import (
    initialize_parallel_model,
    neuronx_distributed_config,
)


def _tiny_cfg(**over):
    base = dict(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=32, use_flash_attention=False,
        remat_policy=None,
    )
    base.update(over)
    return LlamaConfig(**base)


def _model(tp=2):
    cfg = neuronx_distributed_config(tensor_parallel_size=tp)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (4, 16)))
    model = initialize_parallel_model(cfg, lambda: LlamaForCausalLM(_tiny_cfg()), ids)
    return model, ids


def _quantized_leaves(qparams):
    return {
        jax.tree_util.keystr(p): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(
            qparams, is_leaf=lambda x: isinstance(x, QuantizedLeaf)
        )[0]
        if isinstance(leaf, QuantizedLeaf)
    }


def test_int8_forward_close_to_fp_golden():
    model, ids = _model()
    fp_logits = np.asarray(model.apply(model.params, ids), np.float32)
    qparams = quantize_params(model.params)
    q_logits = np.asarray(
        quantized_apply(model.module, qparams, ids, dtype=jnp.float32), np.float32
    )
    # int8 weight-only: logits agree to quantization noise; greedy tokens agree
    err = np.abs(q_logits - fp_logits).max() / (np.abs(fp_logits).max() + 1e-9)
    assert err < 0.1, f"relative error {err}"
    agree = (q_logits.argmax(-1) == fp_logits.argmax(-1)).mean()
    assert agree > 0.9, f"greedy agreement {agree}"


def test_targets_and_exclusions():
    model, ids = _model()
    qparams = quantize_params(model.params)
    leaves = _quantized_leaves(qparams)
    assert leaves, "nothing quantized"
    for pstr in leaves:
        assert "embed" not in pstr and "lm_head" not in pstr and "norm" not in pstr
        assert leaves[pstr]["qweight"].dtype == jnp.int8


def test_per_channel_scale_shapes_fan_in_only():
    """(H,N,D) GQA kernel → scale (1,N,D) (per head+dim output channel);
    (E,H,I) expert kernel → scale (E,1,I) (per expert+out channel) —
    ADVICE r1: reduce over the fan-in dim only."""
    params = {
        "attention": {"qkv": {"q_kernel": jnp.ones((16, 4, 8))}},
        "moe": {"expert_mlps": {"down_kernel": jnp.ones((4, 16, 8))}},
    }
    q = quantize_params(params)
    assert q["attention"]["qkv"]["q_kernel"]["scale"].shape == (1, 4, 8)
    assert q["moe"]["expert_mlps"]["down_kernel"]["scale"].shape == (4, 1, 8)


def test_quantization_roundtrip_accuracy():
    """dequant(quant(W)) within one quantization step of W, per channel."""
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(64, 32) * np.geomspace(0.01, 10.0, 32), jnp.float32)
    params = {"proj": {"kernel": w}}
    deq = dequantize_params(quantize_params(params), dtype=jnp.float32)
    scale = np.abs(np.asarray(w)).max(axis=0) / 127.0
    err = np.abs(np.asarray(deq["proj"]["kernel"]) - np.asarray(w))
    assert (err <= scale[None, :] * 0.5 + 1e-9).all()


def test_per_tensor_mode():
    params = {"proj": {"kernel": jnp.asarray(np.random.RandomState(1).randn(8, 8), jnp.float32)}}
    q = quantize_params(params, QuantizationConfig(quantization_type="per_tensor_symmetric"))
    assert q["proj"]["kernel"]["scale"].shape == ()


def test_stacked_kernel_scales_are_per_layer():
    """Scan-stacked kernels (L, ...) must keep fan-in at axis 1: reducing the
    layer axis would share one scale across layers and store a fan_in-sized
    scale tensor (r1 review fix)."""
    import re

    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization.core import (
        QuantizationConfig,
        quantize_params,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=32,
                      use_flash_attention=False, remat_policy=None)
    model = LlamaForCausalLM(cfg)
    from flax.core import meta

    ids = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    qp = quantize_params(params, QuantizationConfig())
    blk = qp["model"]["layers"]["block"]
    # stacked 3D mlp kernel (L, in, out) -> scale (L, 1, out)
    gate = blk["mlp"]["gate_proj"]["kernel"]
    assert gate["qweight"].shape == (2, 32, 64)
    assert gate["scale"].shape == (2, 1, 64)
    # stacked 4D GQA kernel (L, in, n, d) -> scale (L, 1, n, d)
    qk = blk["attention"]["qkv"]["q_kernel"]
    assert qk["scale"].shape == (2, 1, 4, 8)


def test_int8_generate_close_to_fp(tmp_path):
    """End-to-end int8 serving through CausalLM's param_transform hook
    (reference run_llama_quantized.py): greedy int8 generation stays close
    to the fp golden — identical first tokens on a well-separated argmax."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization.core import (
        dequantize_params,
        quantize_params,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=64,
                      dtype=jnp.float32, use_flash_attention=False,
                      remat_policy=None)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, 127),
                     np.int32)
    model = LlamaForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(ids)))["params"]

    lm_fp = CausalLM(cfg, params, LlamaForCausalLM, buckets=(8,), max_batch=1)
    golden = lm_fp.generate(ids, max_new_tokens=6)

    qparams = quantize_params(params)
    lm_q = CausalLM(cfg, qparams, LlamaForCausalLM, buckets=(8,), max_batch=1,
                    param_transform=lambda p: dequantize_params(p, cfg.dtype))
    out = lm_q.generate(ids, max_new_tokens=6)
    # int8 rounding can flip near-tie argmaxes late in the chain; the first
    # tokens (largest margins) must agree and all outputs must be valid
    assert out.tokens[0, 0] == golden.tokens[0, 0]
    assert (out.tokens[0] >= 0).all() and (out.tokens[0] < 128).all()


def test_int8_direct_in_layer_dequant():
    """The fast serving path: the quantized tree feeds the model with NO
    param_transform — the parallel layers dequantize {'qweight','scale'}
    leaves in-layer (inside the scan body for stacked kernels), so the int8
    stack never materializes as bf16 up front. Must match the
    param_transform path bit-for-bit (same dequant math, same dtype)."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization.core import (
        dequantize_params,
        quantize_params,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=64,
                      dtype=jnp.float32, use_flash_attention=False,
                      remat_policy=None)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, 127),
                     np.int32)
    model = LlamaForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(ids)))["params"]
    qparams = quantize_params(params)

    # training-style forward: quantized tree straight through module.apply
    direct = model.apply({"params": qparams}, jnp.asarray(ids))
    via_transform = model.apply(
        {"params": dequantize_params(qparams, cfg.dtype)}, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(direct), np.asarray(via_transform),
                               rtol=1e-6, atol=1e-6)

    # serving: no param_transform
    lm_direct = CausalLM(cfg, qparams, LlamaForCausalLM, buckets=(8,), max_batch=1)
    out_d = lm_direct.generate(ids, max_new_tokens=6)
    lm_t = CausalLM(cfg, qparams, LlamaForCausalLM, buckets=(8,), max_batch=1,
                    param_transform=lambda p: dequantize_params(p, cfg.dtype))
    out_t = lm_t.generate(ids, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out_d.tokens), np.asarray(out_t.tokens))


def test_int8_moe_expert_quantization():
    """MoE int8 serving: the fused expert tensors (leaves gate/up/down)
    quantize by default, the router stays float (routing is the most
    quantization-sensitive op), and the all-experts path (which serving keeps
    for quantized leaves) consumes the quantized tree directly."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    from neuronx_distributed_tpu.quantization.core import quantize_params

    cfg = MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=32, dtype=jnp.float32,
        use_flash_attention=False, num_experts=4, top_k=2, remat_policy=None)
    ids = jnp.asarray(np.random.RandomState(0).randint(1, 127, (1, 8)))
    model = MixtralForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    qp = quantize_params(params)
    flat = {jax.tree_util.keystr(p): l for p, l in
            jax.tree_util.tree_flatten_with_path(
                qp, is_leaf=lambda x: isinstance(x, dict) and "qweight" in x)[0]}
    expert_q = [k for k, v in flat.items()
                if isinstance(v, dict) and ("gate" in k or "down" in k)]
    router_q = [k for k, v in flat.items()
                if isinstance(v, dict) and "router" in k]
    assert expert_q, "expert tensors not quantized"
    assert not router_q, "router must stay float"
    out = model.apply({"params": qp}, ids)
    golden = model.apply({"params": params}, ids)
    # int8 experts track the float forward closely on tiny dims
    assert np.isfinite(np.asarray(out)).all()
    assert np.argmax(np.asarray(out)[0, -1]) == np.argmax(np.asarray(golden)[0, -1])


def test_int8_session_api():
    """start_session/insert/step through the param_transform hook (r2 review:
    the session path bypassed the transform)."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import CausalLM
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.quantization.core import (
        dequantize_params,
        quantize_params,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=32,
                      dtype=jnp.float32, use_flash_attention=False,
                      remat_policy=None)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (1, 8), 1, 127),
                     np.int32)
    model = LlamaForCausalLM(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), jnp.asarray(ids)))["params"]
    lm = CausalLM(cfg, quantize_params(params), LlamaForCausalLM, buckets=(8,),
                  max_batch=2,
                  param_transform=lambda p: dequantize_params(p, cfg.dtype))
    session = lm.start_session()
    logits = lm.insert(session, [0], ids)
    cur = np.zeros((2,), np.int32)
    cur[0] = int(jnp.argmax(logits[0]))
    out = lm.step(session, cur)
    assert np.isfinite(np.asarray(out[0], np.float32)).all()
