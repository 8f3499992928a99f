"""LongCat-Flash (``meituan-longcat/LongCat-Flash-Chat``): a shortcut-connected
block over latent attention, and a router some of whose experts cost nothing.

One published layer holds TWO latent-attention sub-layers ``A_0, A_1``, TWO
dense gated-SiLU MLPs ``D_0, D_1`` of ``ffn_hidden_size`` and ONE expert layer
``M`` that branches off after the first attention and rejoins after the second
MLP (shortcut-connected MoE: the dense path ``D_0 -> A_1 -> D_1`` runs beside
it), with four RMSNorms:

    h = x + A_0(norm_in0(x));   u = norm_post0(h);   m = M(u)
    h = h + D_0(u);   h = h + A_1(norm_in1(h));   y = h + D_1(norm_post1(h)) + m

    M(u): s = softmax(u W_r) over ``router_experts + zero_experts``, float32;
    the ``top_k`` largest of ``s + e_score_correction_bias``; a chosen ``e``
    weighs ``routed_scaling_factor x s_e`` (not renormalised);
    M(u) = sum_{e chosen, real, held} w_e expert_e(u) + (sum_{e chosen, identity} w_e) u

What is this file's and what is the stack's:

* **The attention** is ``models/deepseek_v2.py::DeepseekV2Attention`` (expanded
  prompts through the flash kernel, absorbed decode over latent pages) with the
  two low-rank paths scaled after their norms (``mla_scale_q_lora``,
  ``mla_scale_kv_lora``: ``sqrt(hidden_size / rank)``), plain rope.
* **A sub-layer is a cache layer.** The latent leaf is stacked over ``2 x
  num_layers`` (``LongcatFlashConfig.kv_layers``, asked by ``LlamaModel``) and
  sub-layer ``i`` of layer ``l`` reads and writes leaf ``2 l + i``; each has
  its own ``cache_index`` and ``block_table`` (under ``sub_<i>/attention``),
  which everything that moves a row's cache finds by suffix. One block table a row,
  one page pool, no new cache class.
* **The stack** is ``LlamaModel``'s one scan over layers; the body
  (:class:`LongcatFlashLayer`) holds both sub-layers and carries ``m`` across
  the second.
* **The experts** are ``moe/layer.py::MoE`` holding a share of the real experts
  (``router_experts``, ``experts_held_first``) with ``zero_experts`` identity
  experts after them in the router and a selection bias.

Refused (``ValueError``): leading dense layers, shared experts, router groups,
a rope scaling, int8 latent pages (``DeepseekV2Config``). Serving on one chip:
no spec partitions the latent projections across ``tp``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from flax import linen as nn

from neuronx_distributed_tpu.models.deepseek_v2 import DeepseekV2Attention, DeepseekV2Config
from neuronx_distributed_tpu.models.llama import KVLayerView, LlamaForCausalLM, LlamaMLP
from neuronx_distributed_tpu.models.mixtral import MixtralDecoderLayer
from neuronx_distributed_tpu.moe.layer import MoE

SUB_LAYERS = 2      # attention sub-layers, and dense MLPs, of one layer


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig(DeepseekV2Config):
    # ``intermediate_size`` is the published ``ffn_hidden_size`` (each of the
    # two dense MLPs), ``moe_intermediate_size`` ``expert_ffn_hidden_size``,
    # ``num_experts`` the real experts HELD here of ``router_experts``,
    # ``top_k`` ``moe_topk`` of ``router_experts + zero_experts``
    zero_experts: int = 256                 # published ``zero_expert_num``, type identity
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    router_selection_bias: bool = True      # ``e_score_correction_bias``
    moe_intermediate_size: int = 2048
    num_experts: int = 512
    top_k: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    first_k_dense: int = 0
    n_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        super().__post_init__()
        for what, asked in (("leading dense layers (first_k_dense)", self.first_k_dense),
                            ("shared experts (n_shared_experts)", self.n_shared_experts),
                            ("router groups (n_group)", self.n_group != 1),
                            ("a rope scaling (plain rope as published)", self.rope_scaling)):
            if asked:
                raise ValueError(f"LongCat-Flash has no {what}")
        if self.zero_experts < 0:
            raise ValueError(f"zero_experts {self.zero_experts}")
        # the published modelling code's form; the config gives the booleans
        for name, on, rank in (("q_lora_scale", self.mla_scale_q_lora, self.q_lora_rank),
                               ("kv_lora_scale", self.mla_scale_kv_lora, self.kv_lora_rank)):
            object.__setattr__(self, name, (self.hidden_size / rank) ** 0.5 if on else 1.0)

    @property
    def kv_layers(self) -> int:
        """Cache layers of the stack: a sub-layer each (``LlamaModel``)."""
        return SUB_LAYERS * self.num_layers


def longcat_flash_chat(**over) -> LongcatFlashConfig:
    """meituan-longcat/LongCat-Flash-Chat: 560 B parameters, 18.6-31.3 B active."""
    return LongcatFlashConfig(**{**dict(
        vocab_size=131072, hidden_size=6144, intermediate_size=12288, num_layers=28,
        num_heads=64, num_kv_heads=64, rope_theta=1e7, max_seq_len=4096,
    ), **over})


class LongcatFlashSubLayer(nn.Module):
    """A latent attention and a dense MLP with their two norms, in two steps
    so that the layer can take its expert branch off between them. The
    submodules keep the names every stack here gives them (``attention``,
    ``mlp``, ``input_norm``, ``post_attn_norm``), which is what a device trace
    is sorted by (``benchmark/scope_parts.json``)."""

    config: LongcatFlashConfig

    def setup(self):
        cfg = self.config
        self.input_norm = cfg.make_norm()
        self.attention = DeepseekV2Attention(cfg)
        self.post_attn_norm = cfg.make_norm()
        self.mlp = LlamaMLP(cfg)

    def attend(self, x, rope, kv=None, live=None):
        """``(h, u)``: the residual after the attention, and its norm."""
        h = x + self.attention(self.input_norm(x), rope, kv=kv, live=live)
        return h, self.post_attn_norm(h)

    def feed(self, h, u):
        return h + self.mlp(u)


class LongcatFlashLayer(nn.Module):
    """One published layer: both sub-layers, the expert layer a branch over
    the second of them."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope, kv=None, live=None, stack=None) -> jax.Array:
        cfg = self.config
        m = None
        for i in range(SUB_LAYERS):
            sub = LongcatFlashSubLayer(cfg, name=f"sub_{i}")
            view = None if kv is None else KVLayerView(SUB_LAYERS * kv.layer + i, kv.leaves)
            x, u = sub.attend(x, rope, view, live)
            if view is not None:
                kv.leaves = view.leaves
            if i == 0:
                with jax.named_scope("scmoe_branch"):
                    m = self._experts(u, live, None if stack is None else (kv.layer, stack))
            x = sub.feed(x, u)
        return x + m

    def _experts(self, h, live, stack):
        cfg = self.config
        return MoE(
            num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size, top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, router=cfg.router, mode=cfg.moe_mode,
            capacity_factor=cfg.capacity_factor, sequence_parallel=cfg.sequence_parallel,
            aux_loss_coef=cfg.aux_loss_coef, z_loss_coef=cfg.z_loss_coef, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, inference=cfg.decode,
            router_experts=cfg.router_experts, experts_held_first=cfg.experts_held_first,
            route_scale=cfg.routed_scaling_factor, zero_experts=cfg.zero_experts,
            selection_bias=cfg.router_selection_bias, name="moe",
        )(h, live, stack)

    layer_stack = staticmethod(MixtralDecoderLayer.layer_stack)


class LongcatFlashForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM`` (embedding, the layer scan, final norm, untied
    vocab-parallel head) over :class:`LongcatFlashLayer`."""

    layer_cls: Any = LongcatFlashLayer
