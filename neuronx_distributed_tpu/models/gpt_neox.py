"""GPT-NeoX model family (parallel-residual decoder), TP-parallel.

Capability-parity with the reference's GPT-NeoX pretraining examples
(``examples/training/tp_dp_gpt_neox_hf_pretrain`` — 6.9B and 20B TP+ZeRO1
configs over HF ``GPTNeoXForCausalLM`` with parallel-linear surgery).
Architecture (vs Llama): PARALLEL residual ``x + attn(ln1(x)) + mlp(ln2(x))``,
LayerNorm (with bias) instead of RMSNorm, biased QKV/MLP projections, plain
GELU MLP, and PARTIAL rotary embeddings (``rotary_pct`` of each head dim).
The embed/scan/head stack is the shared Llama one (``layer_cls``)."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    apply_rotary,
)
from neuronx_distributed_tpu.ops.attention import attention
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    GQAQKVColumnParallelLinear,
    RowParallelLinear,
    SPLayerNorm,
)


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig(LlamaConfig):
    rotary_pct: float = 0.25
    use_parallel_residual: bool = True
    layer_norm_eps: float = 1e-5
    norm_type: str = "layernorm"  # NeoX's final norm is biased LayerNorm

    @property
    def rope_dims(self) -> int:
        # tables built ONCE at the model level for the partial-rotary dims
        # (NeoX frequencies use rotary_dims as the denominator base)
        return int(self.head_dim_ * self.rotary_pct)


def gpt_neox_6_9b(**over) -> GPTNeoXConfig:
    return GPTNeoXConfig(**{**dict(
        vocab_size=50432, hidden_size=4096, intermediate_size=16384,
        num_layers=32, num_heads=32, num_kv_heads=32, rotary_pct=0.25,
    ), **over})


def gpt_neox_20b(**over) -> GPTNeoXConfig:
    return GPTNeoXConfig(**{**dict(
        vocab_size=50432, hidden_size=6144, intermediate_size=24576,
        num_layers=44, num_heads=64, num_kv_heads=64, rotary_pct=0.25,
    ), **over})


def apply_partial_rotary(x: jax.Array, cos, sin, rotary_dims: int) -> jax.Array:
    """Rotate only the first ``rotary_dims`` of each head (GPT-NeoX
    ``rotary_pct``); the remainder passes through unrotated. ``cos``/``sin``
    must be built FOR ``rotary_dims`` (NeoX frequencies use rotary_dims as
    the denominator base — slicing a full-head-dim table would change the
    frequency spectrum)."""
    if rotary_dims >= x.shape[-1]:
        return apply_rotary(x, cos, sin)
    rot, rest = x[..., :rotary_dims], x[..., rotary_dims:]
    return jnp.concatenate([apply_rotary(rot, cos, sin), rest], axis=-1)


class GPTNeoXAttention(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope) -> jax.Array:
        cfg = self.config
        if cfg.decode:
            raise NotImplementedError(
                "GPT-NeoX decode/KV-cache serving: use the Llama-family serving "
                "stack (the reference's NeoX support is training-only examples)"
            )
        q, k, v = GQAQKVColumnParallelLinear(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim_,
            use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="qkv",
        )(x)
        # the stack builds the tables ONCE for cfg.rope_dims (rotary_dims-based
        # NeoX frequencies) and broadcasts them through the scan
        cos, sin = rope
        rd = cfg.rope_dims
        q = apply_partial_rotary(q, cos, sin, rd)
        k = apply_partial_rotary(k, cos, sin, rd)
        s = x.shape[1]
        if cfg.context_parallel:  # same CP routing as the Llama attention
            from neuronx_distributed_tpu.ops.ring_attention import ring_attention

            o = ring_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                layout=cfg.cp_layout,
                block_q=cfg.attention_block_q, block_k=cfg.attention_block_k,
            )
        else:
            from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

            blk_q, blk_k = cfg.blocks_for(s)
            o = attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
                use_flash=cfg.use_flash_attention and flash_supported(s, s, blk_q, blk_k),
                block_q=blk_q, block_k=blk_k,
            )
        o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], s, -1)
        return RowParallelLinear(
            cfg.hidden_size, use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="o_proj",
        )(o)


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        h = ColumnParallelLinear(
            cfg.intermediate_size, use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="up",
        )(x)
        return RowParallelLinear(
            cfg.hidden_size, use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="down",
        )(nn.gelu(h, approximate=False))


class GPTNeoXDecoderLayer(nn.Module):
    """Parallel residual: ``x + attn(ln1(x)) + mlp(ln2(x))`` (GPT-NeoX's
    signature deviation from the serial Llama block); serial form available
    via ``use_parallel_residual=False``."""

    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope) -> jax.Array:
        cfg = self.config
        h_attn = SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype,
                             sequence_parallel=cfg.sequence_parallel,
                             name="input_norm")(x)
        attn_out = GPTNeoXAttention(cfg, name="attention")(h_attn, rope)
        if cfg.use_parallel_residual:
            h_mlp = SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                sequence_parallel=cfg.sequence_parallel,
                                name="post_attn_norm")(x)
            return x + attn_out + GPTNeoXMLP(cfg, name="mlp")(h_mlp)
        x = x + attn_out
        h_mlp = SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            sequence_parallel=cfg.sequence_parallel,
                            name="post_attn_norm")(x)
        return x + GPTNeoXMLP(cfg, name="mlp")(h_mlp)


class GPTNeoXForCausalLM(LlamaForCausalLM):
    """The shared embed/scan/head stack with the NeoX decoder block: the
    stack's rope tables cover ``rope_dims`` (partial rotary) and the final
    norm is NeoX's biased LayerNorm (``norm_type``)."""

    layer_cls: Any = GPTNeoXDecoderLayer
