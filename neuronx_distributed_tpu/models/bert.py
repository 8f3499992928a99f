"""BERT model family (encoder + pretraining heads), TP-parallel.

Capability-parity with the reference's BERT pretraining example
(``examples/training/tp_dp_bert_large_hf_pretrain_hdf5.py`` — HF
``BertForPreTraining`` with ``ParallelSelfAttention``/``ParallelSelfOutput``
surgery at :344-383, MLM+NSP losses, tied MLM decoder) re-designed for TPU:

* one flax module tree; TP sharding declared on the weights
  (Column/RowParallel + vocab-sharded ``ParallelEmbedding``), GSPMD places
  the collectives — no per-layer module surgery;
* bidirectional attention with a padding mask runs through the same Pallas
  flash kernel as the causal models (position-based masking: a masked key
  gets position ``seq`` which no query can see), with a dense fallback for
  unsupported shapes;
* the MLM decoder is tied to the word embedding (``attend``) and its loss is
  the vocab-parallel CE — logits never gather over TP.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.kernels.flash_attn import flash_supported
from neuronx_distributed_tpu.ops.attention import attention
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    GQAQKVColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
    SPLayerNorm,
)
from neuronx_distributed_tpu.parallel.loss import parallel_cross_entropy_mean
from neuronx_distributed_tpu.parallel.partitioning import ACT_FULL, constrain


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_flash_attention: bool = True
    attention_block_q: int = 128
    attention_block_k: int = 128
    remat_policy: Optional[str] = None
    sequence_parallel: bool = False  # accepted for config parity; encoder runs full-seq
    # explicit head_dim override (head padding appends heads, after which
    # hidden_size // num_heads no longer equals it — same contract as Llama)
    head_dim: Optional[int] = None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


def bert_large(**over) -> BertConfig:
    """L24_A16_H1024 — the reference example's target size
    (``tp_dp_bert_large_hf_pretrain_hdf5.py``)."""
    return BertConfig(**{**dict(hidden_size=1024, intermediate_size=4096,
                                num_layers=24, num_heads=16), **over})


def bert_base(**over) -> BertConfig:
    return BertConfig(**{**dict(hidden_size=768, intermediate_size=3072,
                                num_layers=12, num_heads=12), **over})


class BertSelfAttention(nn.Module):
    """Bidirectional TP attention. ``attention_mask``: (b, s) 1=token 0=pad."""

    config: BertConfig

    @nn.compact
    def __call__(self, x: jax.Array, attention_mask: jax.Array) -> jax.Array:
        cfg = self.config
        q, k, v = GQAQKVColumnParallelLinear(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_heads,
            head_dim=cfg.head_dim_,
            use_bias=True,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="qkv",
        )(x)
        b, s = x.shape[0], x.shape[1]
        # padding mask → kernel position mask: queries sit at position s-1,
        # valid keys at 0, masked keys at s (invisible to every query)
        kv_positions = jnp.where(attention_mask.astype(bool), 0, s).astype(jnp.int32)
        q_positions = jnp.full((b, s), s - 1, jnp.int32)
        use_flash = cfg.use_flash_attention and flash_supported(
            s, s, cfg.attention_block_q, cfg.attention_block_k
        )
        o = attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=False,
            use_flash=use_flash,
            block_q=cfg.attention_block_q,
            block_k=cfg.attention_block_k,
            q_positions=q_positions,
            kv_positions=kv_positions,
        )
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return RowParallelLinear(
            cfg.hidden_size, use_bias=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="output",
        )(o)


class BertLayer(nn.Module):
    """Post-LN encoder block (BERT ordering: LN(x + sublayer(x)))."""

    config: BertConfig

    @nn.compact
    def __call__(self, x: jax.Array, attention_mask: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.config
        h = BertSelfAttention(cfg, name="attention")(x, attention_mask)
        h = nn.Dropout(cfg.hidden_dropout)(h, deterministic=deterministic)
        x = SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="attention_norm")(x + h)
        h = ColumnParallelLinear(
            cfg.intermediate_size, use_bias=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="intermediate",
        )(x)
        h = nn.gelu(h, approximate=False)
        h = RowParallelLinear(
            cfg.hidden_size, use_bias=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_output",
        )(h)
        h = nn.Dropout(cfg.hidden_dropout)(h, deterministic=deterministic)
        return SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="output_norm")(x + h)


class _BertLayerStep(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask, deterministic):
        cls = BertLayer
        if self.config.remat_policy is not None:
            from neuronx_distributed_tpu.models.llama import _remat_policy

            # static_argnums counts the bound module as arg 0, so
            # ``deterministic`` in ``(self, x, mask, deterministic)`` is 3
            cls = nn.remat(cls, policy=_remat_policy(self.config.remat_policy),
                           prevent_cse=False, static_argnums=(3,))
        return cls(self.config, name="block")(x, attention_mask, deterministic), None


class BertModel(nn.Module):
    """Embeddings (word + position + token-type, LN, dropout) + scanned
    encoder stack. Returns (sequence_output, pooled_output)."""

    config: BertConfig

    def setup(self):
        cfg = self.config
        self.word_embeddings = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, shard_over="vocab",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        self.position_embeddings = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        self.token_type_embeddings = nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        self.embed_norm = SPLayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        self.embed_dropout = nn.Dropout(cfg.hidden_dropout)
        self.layers = nn.scan(
            _BertLayerStep,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            length=cfg.num_layers,
            in_axes=(nn.broadcast, nn.broadcast),
            metadata_params={nn.meta.PARTITION_NAME: None},
        )(cfg)
        # pooler: tanh(dense([CLS])) — replicated head
        self.pooler = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype)

    def __call__(self, input_ids: jax.Array, token_type_ids: Optional[jax.Array] = None,
                 attention_mask: Optional[jax.Array] = None,
                 deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), jnp.int32)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((b, s), jnp.int32)
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(jnp.arange(s, dtype=jnp.int32))
             + self.token_type_embeddings(token_type_ids))
        x = self.embed_norm(x)
        x = self.embed_dropout(x, deterministic=deterministic)
        x = constrain(x, ACT_FULL)
        x, _ = self.layers(x, attention_mask, deterministic)
        pooled = jnp.tanh(self.pooler(x[:, 0]))
        return x, pooled

    def attend(self, x: jax.Array) -> jax.Array:
        return self.word_embeddings.attend(x)


class BertForPreTraining(nn.Module):
    """MLM + NSP heads (HF ``BertForPreTraining`` surface the reference
    example trains). The MLM decoder is tied to the word embedding, its bias
    is a separate vocab-sharded param (the reference re-ties
    ``cls.predictions.decoder.bias`` explicitly); logits stay vocab-sharded
    into the parallel CE."""

    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True):
        cfg = self.config
        bert = BertModel(cfg, name="bert")
        x, pooled = bert(input_ids, token_type_ids, attention_mask, deterministic)
        # MLM transform: dense + gelu + LN, then tied decoder
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="mlm_transform")(x)
        h = nn.gelu(h, approximate=False)
        h = SPLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="mlm_norm")(h)
        from neuronx_distributed_tpu.parallel.mesh import TP_AXIS

        mlm_bias = self.param(
            "mlm_bias", nn.with_partitioning(nn.initializers.zeros_init(), (TP_AXIS,)),
            (cfg.vocab_size,), cfg.param_dtype,
        )
        prediction_logits = bert.attend(h) + mlm_bias.astype(h.dtype)
        seq_relationship_logits = nn.Dense(
            2, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="nsp_head",
        )(pooled)
        return prediction_logits, seq_relationship_logits

    def loss(self, input_ids, masked_lm_labels, next_sentence_labels,
             token_type_ids=None, attention_mask=None, deterministic: bool = True,
             ignore_index: int = -100) -> jax.Array:
        """Total pretraining loss = MLM CE (ignore_index-masked, vocab-parallel)
        + NSP CE (the HF head's summed loss the reference trains against)."""
        mlm_logits, nsp_logits = self(input_ids, token_type_ids, attention_mask,
                                      deterministic)
        mlm_loss = parallel_cross_entropy_mean(
            mlm_logits, masked_lm_labels, ignore_index=ignore_index
        )
        nsp_logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nsp_logp, next_sentence_labels[:, None], axis=-1)
        )
        return mlm_loss + nsp_loss
