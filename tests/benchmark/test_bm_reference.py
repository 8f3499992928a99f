"""The plain references against the program's own float32 forward, tiny widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import gpt_neox, mistral_family

KW = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
          num_kv_heads=2, max_seq_len=64, dtype=jnp.float32, use_flash_attention=False,
          remat_policy=None)
IDS = np.random.RandomState(0).randint(1, 512, (2, 24)).astype(np.int32)


def program(model_cls, cfg):
    from neuronx_distributed_tpu.parallel import mesh

    mesh.initialize_model_parallel(tensor_model_parallel_size=1, devices=jax.devices()[:1])
    model = model_cls(cfg)
    params = meta.unbox(model.init(jax.random.key(1), jnp.asarray(IDS)))["params"]

    def shake(path, a):                       # zero-initialised biases would hide a dropped one
        if "bias" in jax.tree_util.keystr(path):
            return a + 0.1 * jax.random.normal(jax.random.key(3), a.shape)
        return a

    return model, jax.tree_util.tree_map_with_path(shake, params)


def families():
    from neuronx_distributed_tpu.models.gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    return {
        "mistral": (LlamaForCausalLM, LlamaConfig(**KW, rope_theta=1e6), mistral_family,
                    {"rope_theta": 1e6, "rms_norm_eps": 1e-5}),
        "mixtral": (MixtralForCausalLM,
                    MixtralConfig(**KW, num_experts=4, top_k=2, rope_theta=1e6,
                                  moe_mode="all_experts"), mistral_family,
                    {"rope_theta": 1e6, "rms_norm_eps": 1e-5, "num_experts_per_tok": 2}),
        "pythia": (GPTNeoXForCausalLM, GPTNeoXConfig(**{**KW, "num_kv_heads": 4}), gpt_neox,
                   {"layer_norm_eps": 1e-5, "rotary_pct": 0.25, "rotary_emb_base": 10000,
                    "num_attention_heads": 4, "hidden_size": 64}),
    }


@pytest.mark.parametrize("family", ["mistral", "mixtral", "pythia"])
def test_reference_forward_equals_the_programs_float32_forward(family):
    model_cls, cfg, ref, sizes = families()[family]
    model, params = program(model_cls, cfg)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)))
    want = np.asarray(ref.forward(params, jnp.asarray(IDS), sizes))
    # float32 against float32: only the order of additions differs
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_reference_positions_pick_rows_of_the_full_logits():
    model_cls, cfg, ref, sizes = families()["mixtral"]
    _, params = program(model_cls, cfg)
    full = np.asarray(ref.forward(params, jnp.asarray(IDS), sizes))
    pick = np.asarray([[3, 23], [0, 7]])
    some = np.asarray(ref.forward(params, jnp.asarray(IDS), sizes, positions=pick))
    assert np.allclose(some, full[np.arange(2)[:, None], pick], atol=1e-5)


def test_reference_loss_equals_the_programs_loss():
    model_cls, cfg, ref, sizes = families()["pythia"]
    model, params = program(model_cls, cfg)
    labels = jnp.asarray(np.roll(IDS, -1, axis=1))
    with jax.default_matmul_precision("highest"):
        got = float(model.apply({"params": params}, jnp.asarray(IDS), labels, method=model_cls.loss))
    assert float(ref.loss(params, jnp.asarray(IDS), labels, sizes)) == pytest.approx(got, rel=1e-5)
