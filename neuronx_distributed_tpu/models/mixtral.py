"""Mixtral model family: Llama-architecture attention + MoE FFN.

Capability-parity with the reference's Mixtral support
(``examples/training/mixtral`` training preset and the
``examples/inference/mixtral`` serving stack over ``modules/moe``): same
GQA attention as Llama (reused directly — the reference subclasses its Llama
attention too), each decoder layer's MLP replaced by the MoE block with
top-k routing, load-balancing aux loss summed into the training loss.

Serving (``config.decode``) runs the experts as one dropless grouped matmul
over the (token, expert) assignments sorted by expert, in prefill and in
decode (``moe/layer.py``, ``moe/expert_mlps.py::forward_grouped``,
``kernels/grouped_matmul.py``): Mixtral (8 experts, top-2), DBRX (16, top-4)
and OLMoE (``models/olmoe.py``: 64, top-8) read the experts their real tokens
chose and do ``top_k / E`` of the all-experts FLOPs. Which tokens are real
comes down the stack as ``live`` (b, s): the serving programs of
``inference/causal_lm.py`` know it (live decode rows, a prompt's own
positions), and a token that is not real chooses nothing.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
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
    moe_mode: str = "capacity_factor"  # | "all_experts"; serving: moe/layer.py
    capacity_factor: float = 1.25
    router: str = "top_k"
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.0
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
    def __call__(self, x: jax.Array, rope, kv=None, live=None,
                 stack=None) -> jax.Array:
        cfg = self.config
        h = cfg.make_norm(name="input_norm")(x)
        x = x + LlamaAttention(cfg, name="attention")(h, rope, kv=kv, live=live)
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
            name="moe",
        )(h, live, None if stack is None else (kv.layer, stack))
        return x + moe_out

    @staticmethod
    def layer_stack(block_params):
        """The expert weights of ALL layers, ``(L, E, ...)`` each, out of the
        stacked parameters of the scanned blocks (``LlamaModel.layer_stack``):
        handed to every layer whole, beside its index, so that the grouped
        kernel reads ``[layer, expert]`` blocks out of the stack. The layer's
        own slice of it would reach a kernel as a copy of all ``E`` experts,
        every layer of every step (measured on the v5e: 4.2 of 5.5 s of
        OLMoE's decode blocks). None where there is nothing to hand over: at
        init, or with int8 leaves (``ExpertMLPs`` then runs all_experts, whose
        einsums read the slice in place)."""
        experts = block_params.get("moe", {}).get("experts", {})
        if not experts or any(isinstance(w, Mapping) for w in experts.values()):
            return None
        return dict(experts)


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
