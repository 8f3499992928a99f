"""Mixtral model family: Llama-architecture attention + MoE FFN.

Capability-parity with the reference's Mixtral support
(``examples/training/mixtral`` training preset and the
``examples/inference/mixtral`` serving stack over ``modules/moe``): same
GQA attention as Llama (reused directly — the reference subclasses its Llama
attention too), each decoder layer's MLP replaced by the MoE block with
top-k routing, load-balancing aux loss summed into the training loss, and
token-generation inference dispatching to selective expert loading
(``moe/expert_mlps.py``).

What the dispatch rule (``moe/layer.py``, ``T * top_k / E`` against
``selective_loading_threshold`` 0.5) does by shape: Mixtral (8 experts,
top-2) decodes all-experts from 2 rows up, and 4 rows touch every expert
anyway; DBRX (16, top-4) likewise from 2 rows; OLMoE (``models/olmoe.py``: 64,
top-8) decodes all-experts from 4 rows up, reading all 64 experts where 8 rows
choose at most 64 and 3 about 21. Every prefill is all-experts: ``E / top_k``
= 4 x (Mixtral, DBRX) or 8 x (OLMoE) the expert FLOPs the routing needs.
ROADMAP S4 (one dropless grouped matmul) replaces the rule.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
)
from neuronx_distributed_tpu.moe.layer import MoE, collect_aux_losses
from neuronx_distributed_tpu.parallel.layers import RMSNorm
from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    norm_topk_prob: bool = True  # HF's key; OLMoE: false (models/olmoe.py)
    moe_mode: str = "capacity_factor"  # training/ctx: "capacity_factor" | "all_experts"
    capacity_factor: float = 1.25
    router: str = "top_k"
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.0
    selective_loading_threshold: float = 0.5
    # DBRX serves through this stack with bias-free LayerNorms instead of
    # RMSNorm (HF DbrxBlock norm_1/norm_2/norm_f are nn.LayerNorm(bias=False))
    norm_type: str = "rmsnorm"  # | "layernorm"
    norm_bias: bool = True
    layer_norm_eps: float = 1e-5


def mixtral_8x7b(**over) -> MixtralConfig:
    return MixtralConfig(**{**dict(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1e6,
        num_experts=8, top_k=2,
    ), **over})


def dbrx(**over) -> MixtralConfig:
    """DBRX dims (reference serves it through the same MoE stack,
    ``examples/inference/run_dbrx.py``): 16 experts, top-4 routing."""
    return MixtralConfig(**{**dict(
        vocab_size=100352, hidden_size=6144, intermediate_size=10752,
        num_layers=40, num_heads=48, num_kv_heads=8, rope_theta=5e5,
        num_experts=16, top_k=4,
        # DBRX-specific architecture bits (HF DbrxConfig defaults)
        norm_type="layernorm", norm_bias=False, qkv_clip=8.0,
    ), **over})


class MixtralDecoderLayer(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope, kv=None) -> jax.Array:
        cfg = self.config
        h = cfg.make_norm(name="input_norm")(x)
        x = x + LlamaAttention(cfg, name="attention")(h, rope, kv=kv)
        h = cfg.make_norm(name="post_attn_norm")(x)
        moe_out = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob,
            router=cfg.router,
            mode=cfg.moe_mode,
            capacity_factor=cfg.capacity_factor,
            sequence_parallel=cfg.sequence_parallel,
            aux_loss_coef=cfg.aux_loss_coef,
            z_loss_coef=cfg.z_loss_coef,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            inference=cfg.decode,
            selective_loading_threshold=cfg.selective_loading_threshold,
            name="moe",
        )(h)
        return x + moe_out


class MixtralModel(LlamaModel):
    """The Llama stack with the MoE decoder block (embed/rope/scan/final-norm
    are shared — parameterized by ``layer_cls``, no copy)."""

    layer_cls: Any = MixtralDecoderLayer


class MixtralForCausalLM(LlamaForCausalLM):
    """LlamaForCausalLM with the MoE decoder block: same vocab-parallel head,
    same ``tie_word_embeddings`` handling. The aux (load-balancing) losses
    are sown into the ``"losses"`` collection per layer; use
    :func:`mixtral_loss` to train with them included."""

    layer_cls: Any = MixtralDecoderLayer


def mixtral_loss(module: MixtralForCausalLM, params, input_ids, labels,
                 ignore_index: int = -100) -> jax.Array:
    """CE + sown MoE aux losses (the reference threads the aux loss out of
    the MoE block and adds it in the example training loop,
    ``examples/training/mixtral``)."""
    logits, mut = module.apply({"params": params}, input_ids, mutable=["losses"])
    ce = parallel_cross_entropy_mean(logits, labels, ignore_index=ignore_index)
    return ce + collect_aux_losses(mut)
