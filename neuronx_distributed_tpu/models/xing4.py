"""Xing4.0 (``model_type: xing4_0``, XingChen-AGI/Xing4.0-29B-A4B): DeepSeek-V2's
latent attention and V3's ``noaux_tc`` route (no groups) under FOUR residual
streams mixed by learned doubly-stochastic weights (manifold-constrained
hyper-connections; the config's ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min/max``).

What is this file's and what is the stack's:

* **The streams.** ``LlamaModel`` widens its layer scans' carry to ``hc_mult``
  hidden states, a tuple of ``(batch, tokens, hidden)``, between the embedding
  and the final norm of a config with ``hc_mult`` (``ops/stream_mix.py::
  mhc_expand`` / ``mhc_reduce``: every stream starts as the embedding, the exit
  is their sum). A layer has two sub-blocks (attention; dense MLP, or routed
  experts + shared expert), each with its own input RMSNorm as in every stack
  here and each wrapped by its own
  :class:`~neuronx_distributed_tpu.ops.stream_mix.StreamMix` (``attn_mix``,
  ``ffn_mix``): coefficients from the flattened streams, a Sinkhorn projection
  of the 4 x 4 residual mix a token, read-in, write-back (the equations are in
  ``ops/stream_mix.py``; the reading of the points the config leaves silent is
  argued in ``benchmark/reference/xing4.py`` and the configuration file's
  ``assumed``, nowhere else).
* **The attention**, its one latent cache leaf, YaRN, the expanded prompt and
  the absorbed one-token step are ``models/deepseek_v2.py::DeepseekV2Attention``,
  untouched; so are the paged pool, prefix sharing and park/resume of every
  latent-attention model.
* **The route** is ``moe/routing.py::RouterTopK`` with sigmoid scores and a
  selection bias, ``n_group`` 1: LongCat-Flash's branch of it at V3's scoring.

Refused (``ValueError`` naming ``hc_mult``): ``sequence_parallel`` /
``context_parallel`` (their constraints lay out a three-axis hidden state), a
LoRA pool (no adapter is declared on a stream), ``tp > 1`` (``LlamaModel``),
``models/llama_pipeline.py`` (its stages hand on one hidden state). Not here:
the multi-token-prediction module (``num_nextn_predict_layers``: a training
objective and an optional draft head; serving yields one token a row a step).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from flax import linen as nn

from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Attention, DeepseekV2Config
from neuronx_distributed_tpu.models.llama import KVWalk, LlamaForCausalLM, LlamaMLP, YarnScaling
from neuronx_distributed_tpu.models.mixtral import MixtralDecoderLayer
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.ops.stream_mix import StreamMix

SUB_BLOCKS = 2      # stream mixes a layer: one around the attention, one around the FFN


@dataclasses.dataclass(frozen=True)
class Xing4Config(DeepseekV2Config):
    # the residual path; ``hc_mult`` is what ``LlamaModel`` widens its carry by
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # the published layer
    q_lora_rank: int = 768
    first_k_dense: int = 2
    moe_intermediate_size: int = 1024
    n_shared_experts: int = 1
    num_experts: int = 64
    top_k: int = 4
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    router_selection_bias: bool = True

    def __post_init__(self):
        super().__post_init__()
        if self.hc_mult < 2 or self.hc_sinkhorn_iters < 1:
            raise ValueError(f"hc_mult {self.hc_mult}, hc_sinkhorn_iters {self.hc_sinkhorn_iters}")
        for what, asked in (
                ("sequence_parallel (its constraint lays out a (batch, seq, hidden) state)",
                 self.sequence_parallel),
                ("context_parallel (its constraint lays out a (batch, seq, hidden) state)",
                 self.context_parallel),
                ("lora_rank (no adapter is declared on a residual stream)", self.lora_rank)):
            if asked:
                raise ValueError(
                    f"hc_mult = {self.hc_mult} residual streams are not carried under {what}")

    @property
    def stream_mixes(self) -> int:
        """Stream mixes one token passes through the stack (what the engine
        multiplies its ``mhc_mix_*`` counters by)."""
        return SUB_BLOCKS * self.num_layers

    def stream_walk_sums(self, walk: KVWalk):
        """Of ONE decode step: its live rows times the stack's stream mixes
        (``inference/causal_lm.py::_walk_sums``)."""
        return (self.stream_mixes * walk.live_rows,)

    def mix(self, name: str) -> StreamMix:
        return StreamMix(
            self.hc_mult, self.hidden_size, self.hc_sinkhorn_iters, self.hc_eps,
            (self.mhc_h_res_clamp_min, self.mhc_h_res_clamp_max), self.dtype, self.param_dtype,
            name=name)


def xing4_29b_a4b(**over) -> Xing4Config:
    """XingChen-AGI/Xing4.0-29B-A4B: 29 B parameters, 4 B active."""
    return Xing4Config(**{**dict(
        vocab_size=131072, hidden_size=3584, intermediate_size=9216, num_layers=40,
        num_heads=32, num_kv_heads=32, rope_theta=10000.0, max_seq_len=4096,
        rope_scaling=YarnScaling(
            factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    ), **over})


def _mixed(mix: StreamMix, streams, sub_block):
    """``sub_block`` (norm and all) under ``mix``: read, apply, write back."""
    pre, post, res = mix.coeff(streams)
    return mix.write(streams, sub_block(mix.read(streams, pre)), post, res)


class Xing4DenseLayer(nn.Module):
    """A leading layer: latent attention and a SwiGLU MLP of
    ``intermediate_size``, each under its own stream mix."""

    config: Xing4Config
    attention_cls = DeepseekV2Attention

    @nn.compact
    def __call__(self, x, rope, kv=None, live=None):
        cfg = self.config
        attention = self.attention_cls(cfg, name="attention")
        input_norm, post_attn_norm = cfg.make_norm(name="input_norm"), cfg.make_norm(
            name="post_attn_norm")
        mlp = LlamaMLP(cfg, name="mlp")
        x = _mixed(cfg.mix("attn_mix"), x, lambda u: attention(input_norm(u), rope, kv, live))
        return _mixed(cfg.mix("ffn_mix"), x, lambda u: mlp(post_attn_norm(u)))


class Xing4MoELayer(nn.Module):
    """An expert layer: latent attention, then the routed experts plus the
    shared expert (one sub-block: both read the same normed ``u``, their sum
    is written back once), each sub-block under its own stream mix."""

    config: Xing4Config
    attention_cls = DeepseekV2Attention

    @nn.compact
    def __call__(self, x, rope, kv=None, live=None, stack=None):
        cfg = self.config
        attention = self.attention_cls(cfg, name="attention")
        input_norm, post_attn_norm = cfg.make_norm(name="input_norm"), cfg.make_norm(
            name="post_attn_norm")
        moe = MoE(
            num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size, top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, router=cfg.router, mode=cfg.moe_mode,
            capacity_factor=cfg.capacity_factor, sequence_parallel=cfg.sequence_parallel,
            aux_loss_coef=cfg.aux_loss_coef, z_loss_coef=cfg.z_loss_coef, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, inference=cfg.decode,
            router_experts=cfg.router_experts, experts_held_first=cfg.experts_held_first,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            route_scale=cfg.routed_scaling_factor, scoring_func=cfg.scoring_func,
            selection_bias=cfg.router_selection_bias, group_score=cfg.group_score, name="moe")
        shared = LlamaMLP(dataclasses.replace(
            cfg, intermediate_size=cfg.n_shared_experts * cfg.moe_intermediate_size),
            name="shared_expert") if cfg.n_shared_experts else None

        def experts(u):
            h = post_attn_norm(u)
            out = moe(h, live, None if stack is None else (kv.layer - cfg.first_k_dense, stack))
            if shared is None:
                return out
            with jax.named_scope("shared_expert"):
                return out + shared(h)

        x = _mixed(cfg.mix("attn_mix"), x, lambda u: attention(input_norm(u), rope, kv=kv, live=live))
        return _mixed(cfg.mix("ffn_mix"), x, experts)

    layer_stack = staticmethod(MixtralDecoderLayer.layer_stack)


class Xing4ForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM`` (embedding, the two layer scans over the widened
    carry, final norm, untied vocab-parallel head) over Xing4.0's two kinds of
    layer."""

    layer_cls: Any = Xing4MoELayer
    dense_layer_cls: Any = Xing4DenseLayer
