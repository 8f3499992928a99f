"""Llama-2/3 model family, TP/SP-parallel, flash-attention, scan-over-layers.

Capability-parity with the reference's Llama modeling
(``examples/training/llama/modeling_llama_nxd.py`` — TP attention with
``GQAQKVColumnParallelLinear`` at :238-340, ColumnParallel gate/up +
RowParallel down MLP, sequence-parallel norms) re-designed for TPU:

* one flax module tree; weights declare their sharding
  (``nn.with_partitioning``), GSPMD places the TP collectives;
* decoder layers run under ``nn.scan`` so XLA compiles ONE layer body
  regardless of depth (the reference re-traces all layers into one graph);
* activation checkpointing is ``nn.remat`` with a jax checkpoint policy
  (reference ``utils/activation_checkpoint.py`` predicate wrapping →
  ``remat_policy`` config: "full" | "attention" | None, SURVEY §5.7's
  selective-checkpoint levers);
* attention runs the Pallas flash kernel via ``ops.attention`` (the
  reference's NKI kernel seam).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from neuronx_distributed_tpu.ops.attention import attention
from neuronx_distributed_tpu.ops.stream_mix import mhc_expand, mhc_reduce
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    GQAQKVColumnParallelLinear,
    ParallelEmbedding,
    RMSNorm,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.loss import (
    parallel_cross_entropy,
    parallel_cross_entropy_mean,
)
from neuronx_distributed_tpu.parallel.mesh import TP_AXIS
from neuronx_distributed_tpu.parallel.partitioning import ACT_FULL, ACT_SP, constrain

Dtype = Any


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1 piecewise NTK rope scaling (HF ``rope_type: "llama3"``):
    wavelengths beyond ``original_max_position_embeddings/low_freq_factor``
    stretch by ``factor``, short wavelengths stay, the band between
    interpolates smoothly. Frozen dataclass so configs stay hashable."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling as DeepSeek-V2 publishes it (HF ``rope_scaling``
    ``type: "yarn"``, ``DeepseekV2YarnRotaryEmbedding``): each rotary dim
    blends its own frequency with the same over ``factor``, by a linear ramp
    between the dims whose wavelength makes ``beta_fast`` and ``beta_slow``
    turns in ``original_max_position_embeddings`` positions; cos and sin are
    scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, or
    by ``attention_factor`` where the config gives that number itself (HF
    ``rope_parameters``: Laguna's full-attention layers)."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    attention_factor: Optional[float] = None

    @staticmethod
    def get_mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def frequencies(self, dim: int, theta: float):
        """``(inv_freq (dim / 2,) float32, amplitude of cos and sin)``."""
        orig = self.original_max_position_embeddings

        def correction_dim(turns):
            return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), dim - 1)
        own = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        ramp = np.clip((np.arange(dim // 2) - low) / (max(high - low, 0.001)), 0.0, 1.0)
        inv_freq = own / self.factor * ramp + own * (1.0 - ramp)
        amplitude = (self.get_mscale(self.factor, self.mscale)
                     / self.get_mscale(self.factor, self.mscale_all_dim))
        if self.attention_factor is not None:
            amplitude = float(self.attention_factor)
        return inv_freq.astype(np.float32), amplitude


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None  # Llama-3.1+ long-context rope
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # compute dtype (mixed_precision_config.compute_dtype)
    param_dtype: Any = jnp.float32   # storage dtype (master weights live in optimizer)
    sequence_parallel: bool = False
    # ring-attention context parallelism over the "cp" mesh axis: the
    # sequence stays sharded THROUGH attention (ops/ring_attention.py) — a
    # TPU-native extension beyond the reference (SURVEY §2.3: no CP there)
    context_parallel: bool = False
    # "zigzag": balanced CP schedule — the CALLER must feed ids/labels
    # permuted by ops.ring_attention.zigzag_indices(seq, cp); RoPE positions
    # and the attention mask follow the true (permuted) positions here.
    # "contiguous": plain order, last rank carries ~2x the attention work.
    cp_layout: str = "contiguous"
    use_flash_attention: bool = True
    # None = sequence-adaptive choice (kernels.flash_attn.default_attention_blocks)
    attention_block_q: Optional[int] = None
    attention_block_k: Optional[int] = None
    remat_policy: Optional[str] = "full"  # None | "full" | "attention"
    kv_size_multiplier: int = 1
    tie_word_embeddings: bool = False
    # clamp q/k/v projections to [-qkv_clip, qkv_clip] (DBRX's clip_qkv)
    qkv_clip: Optional[float] = None
    # OLMoE's QK-norm: RMSNorm (eps ``rms_norm_eps``) of the q and of the k
    # projection over ALL heads together, before the split into heads, the
    # clip and the rotary. Off: no parameter and no op.
    qk_norm: bool = False
    # scale of the attention scores before the softmax (Granite's
    # ``attention_multiplier``); None: 1 / sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    # "per-head": each head's attention output is multiplied by
    # ``sigmoid(x W_g)[head]`` before ``o_proj`` (``x`` the layer's normed
    # input, ``W_g`` (hidden, heads): Laguna's ``gating``). None: no
    # parameter and no op.
    attention_gate: Optional[str] = None
    # a query sees its own position and the ``sliding_window - 1`` before it
    # (None: every earlier one). A forward pass masks by it
    # (``flash_attention(window=)``); what a window layer CACHES is a ring a
    # slot, which ``models/laguna.py`` owns: the decode path here refuses.
    sliding_window: Optional[int] = None
    # False: q and k are not rotated (a model without positions, HF
    # ``position_embedding_type: "nope"``)
    use_rope: bool = True
    decode: bool = False  # KV-cache inference mode (cache collection)
    # CE loss sequence-chunking (long-seq memory lever): the head matmul +
    # CE run per chunk of this many tokens when seq exceeds it (None = 4096)
    loss_chunk_size: Optional[int] = None
    # paged KV cache (serving, decode=True only): per-layer page pool of
    # ``page_pool_pages`` pages x ``page_size`` tokens (all layers' pools are
    # one stacked leaf that the layer scan carries: KVLayerView); slot positions
    # resolve through per-slot block tables that RIDE THE CACHE COLLECTION,
    # so compiled programs keep their signatures (inference/paged_cache.py).
    # None = the contiguous max_batch x max_seq_len slab. page_size must
    # divide max_seq_len so that a row's table covers exactly the slab's
    # slots: a prompt attends over the gathered (b, max_seq_len) logical view,
    # a one-token step over whole pages of its live rows up to the longest
    # one's reach (KVWalk), and the slab is read the same two ways, which is what keeps
    # paged attention bit-identical to it.
    page_size: Optional[int] = None
    page_pool_pages: Optional[int] = None
    # paged-pool storage dtype (paged mode only). None = ``dtype``;
    # "int8" stores K/V pages quantized (absmax per page x kv-head, the
    # quantization/core.py convention lifted from weights to KV) with
    # per-(page, head) fp32 scales as sibling cache leaves
    # (``cached_key_scale``/``cached_value_scale``) — ~4x fewer pool
    # bytes than fp32 pages at the same page count, dequantized at the
    # attention read.
    page_dtype: Optional[str] = None
    # multi-LoRA serving pool (inference/adapters.py, S-LoRA/Punica): every
    # targeted projection gains per-slot low-rank stacks A (lora_slots,
    # fan_in, lora_rank) / B (lora_slots, lora_rank, fan_out) + scale on a
    # READ-ONLY "adapters" flax collection (scanned over layers like the
    # cache), and the forward adds y += s[i]·(x @ A[i]) @ B[i] with i =
    # adapter_idx[row] gathered in-program — ONE compiled program serves
    # any adapter mix. Slot 0 is the identity adapter (B = 0, scale = 0:
    # the correction is exactly zero). None disables: no variables are
    # declared and the HLO is byte-identical to the pre-LoRA model.
    lora_rank: Optional[int] = None
    lora_slots: int = 0
    lora_targets: Tuple[str, ...] = ("qkv", "o_proj", "gate_proj",
                                     "up_proj", "down_proj")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rope_dims(self) -> int:
        """Head dims the rotary tables cover (GPT-NeoX's partial rotary
        overrides this to ``rotary_pct * head_dim``)."""
        return self.head_dim_

    def make_norm(self, name: Optional[str] = None):
        """Norm factory honoring ``norm_type``/``norm_bias`` (rmsnorm default;
        GPT-NeoX and DBRX select layernorm) — builds the stack's final norm
        AND every decoder-layer norm, so the selection applies uniformly."""
        if getattr(self, "norm_type", "rmsnorm") == "layernorm":
            from neuronx_distributed_tpu.parallel.layers import SPLayerNorm

            return SPLayerNorm(
                epsilon=getattr(self, "layer_norm_eps", 1e-5), dtype=self.dtype,
                param_dtype=self.param_dtype,
                use_bias=getattr(self, "norm_bias", True),  # DBRX: bias-free
                sequence_parallel=self.sequence_parallel, name=name,
            )
        return RMSNorm(
            epsilon=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            sequence_parallel=self.sequence_parallel, name=name,
        )

    # back-compat name (pre-r3 external callers)
    make_final_norm = make_norm

    def blocks_for(self, sq: int, sk: Optional[int] = None) -> Tuple[int, int]:
        """Flash block sizes: explicit config values, else adaptive — block_q
        keyed on the QUERY length, block_k on the KEY sweep length (``sk``;
        a short prefill into a long cache still sweeps the whole cache).
        Each block shrinks to a divisor of its sequence so the kernel's
        divisibility constraint holds for lengths like 1280 or 4608; when no
        >=128 divisor exists the caller's ``flash_supported`` guard routes
        to the dense path."""
        from neuronx_distributed_tpu.kernels.flash_attn import (
            default_attention_blocks,
            default_prefill_blocks,
        )

        # decode mode never differentiates: prefill uses the fwd-tuned blocks
        pick = default_prefill_blocks if self.decode else default_attention_blocks
        sk = sk or sq
        dq = self.attention_block_q or pick(sq)[0]
        dk = self.attention_block_k or pick(sk)[1]

        def shrink(b: int, s: int) -> int:
            b = min(b, s)
            while b > 128 and s % b:
                b //= 2
            return b

        return shrink(dq, sq), shrink(dk, sk)


# presets mirroring the reference's example configs
def _preset(base, over):
    return LlamaConfig(**{**base, **over})


def llama2_7b(**over) -> LlamaConfig:
    return _preset(dict(hidden_size=4096, intermediate_size=11008, num_layers=32,
                        num_heads=32, num_kv_heads=32), over)


def llama2_13b(**over) -> LlamaConfig:
    return _preset(dict(hidden_size=5120, intermediate_size=13824, num_layers=40,
                        num_heads=40, num_kv_heads=40), over)


def llama2_70b(**over) -> LlamaConfig:
    return _preset(dict(hidden_size=8192, intermediate_size=28672, num_layers=80,
                        num_heads=64, num_kv_heads=8), over)


def llama3_8b(**over) -> LlamaConfig:
    return _preset(dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                        num_layers=32, num_heads=32, num_kv_heads=8,
                        rope_theta=500000.0, max_seq_len=8192), over)


def llama31_8b(**over) -> LlamaConfig:
    """Llama-3.1-8B: 3.0 dims + the long-context rope scaling."""
    return llama3_8b(max_seq_len=over.pop("max_seq_len", 131072),
                     rope_scaling=over.pop("rope_scaling", RopeScaling()), **over)


def llama3_70b(**over) -> LlamaConfig:
    """Llama-3-70B (reference flagship PP workload alongside llama2-70B:
    test/integration/llama3_70B_4layers_PP): llama2-70B dims with the
    Llama-3 vocab/rope."""
    return _preset(dict(vocab_size=128256, hidden_size=8192,
                        intermediate_size=28672, num_layers=80,
                        num_heads=64, num_kv_heads=8,
                        rope_theta=500000.0, max_seq_len=8192), over)


def rotary_embedding(positions: jax.Array, head_dim: int, theta: float,
                     dtype=jnp.float32,
                     scaling: Union[RopeScaling, YarnScaling, None] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given positions, (seq, head_dim/2).
    ``scaling`` applies the Llama-3.1 piecewise frequency stretch (matches
    transformers' ``_compute_llama3_parameters``), or YaRN's blend with its
    amplitude on cos and sin (:class:`YarnScaling`)."""
    if isinstance(scaling, YarnScaling):
        inv_freq, amplitude = scaling.frequencies(head_dim, theta)
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
        return ((jnp.cos(angles) * amplitude).astype(dtype),
                (jnp.sin(angles) * amplitude).astype(dtype))
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        s = scaling
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wl = s.original_max_position_embeddings / s.low_freq_factor
        high_wl = s.original_max_position_embeddings / s.high_freq_factor
        smooth = (s.original_max_position_embeddings / wavelen - s.low_freq_factor) / (
            s.high_freq_factor - s.low_freq_factor)
        interp = (1.0 - smooth) * inv_freq / s.factor + smooth * inv_freq
        inv_freq = jnp.where(wavelen > low_wl, inv_freq / s.factor,
                             jnp.where(wavelen < high_wl, inv_freq, interp))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., s, d/2)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x1, x2) — x is (b, s, n, d); cos/sin (s, d/2) or (b, s, d/2).
    Tables narrower than the head (``cfg.rope_dims < head_dim``) rotate the
    head's first ``2 * cos.shape[-1]`` dims and pass the rest."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rotary(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def cached_attention(q, k_cache, v_cache, cache_len, sm_scale=None):
    """Decode/prefill attention against a fixed-size KV cache.

    ``q``: (b, s_new, n, d) — queries at absolute positions
    ``cache_len .. cache_len+s_new``; ``k_cache``/``v_cache``: (b, S_max,
    n_kv, d); key j is valid for query i iff ``j <= cache_len + i`` AND the
    slot has been written. The reference's KV-cache attention with
    bottom-aligned causal semantics (examples/inference/modules/
    attention_base.py; SURVEY §2.2 inference examples row).

    Grouped by KV head: query head ``h`` reads KV head ``h // group`` through
    a (b, s_new, n_kv, group, d) view of ``q``, so K and V are read once, in
    the dtype the cache holds (widened inside the matmul, never in memory);
    accumulation, scores, mask and softmax are float32."""
    b, s_new, n, d = q.shape
    s_max, n_kv = k_cache.shape[1:3]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))
    # HIGHEST: the MXU would round a float32 operand (the probabilities, a
    # float32 model's q) to bf16; bf16 operands are exact in one pass
    exact = dict(preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)
    scores = jnp.einsum("bikgd,bjkd->bkgij", q.reshape(b, s_new, n_kv, n // n_kv, d),
                        k_cache, **exact) * sm_scale
    qpos = cache_len[:, None] + jnp.arange(s_new)[None, :]      # (b, s_new)
    kpos = jnp.arange(s_max)
    mask = kpos[None, None, :] <= qpos[..., None]               # (b, s_new, s_max)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgij,bjkd->bikgd", probs, v_cache, **exact)
    return out.reshape(b, s_new, n, d).astype(q.dtype)


def _adapter_idx(mdl: nn.Module, batch: int) -> jax.Array:
    """Per-slot adapter index ``(b,)`` riding the read-only ``"adapters"``
    collection exactly like ``cache_index`` rides the cache: the serving
    host swaps it between blocks (or substitutes a row-width view inside
    insert programs) without touching any program signature."""
    return mdl.variable("adapters", "adapter_idx",
                        lambda: jnp.zeros((batch,), jnp.int32)).value


def _lora_pool_delta(mdl: nn.Module, cfg: LlamaConfig, name: str,
                     x: jax.Array, fan_out: int, idx: jax.Array) -> jax.Array:
    """Batched per-row LoRA correction ``s[i] · (x @ A[i]) @ B[i]`` with
    ``i = adapter_idx[row]`` gathered from the device-resident pool stacks
    (S-LoRA's batched adapter matmul). Stacks live on the ``"adapters"``
    collection (per-layer under the scan, like every cache leaf) in fp32 —
    the pool's storage dtype; the caller casts the delta into its own
    compute dtype. Zero-padded ranks and the identity slot's zero B/scale
    contribute exactly zero."""
    pool, r = cfg.lora_slots, cfg.lora_rank
    a = mdl.variable("adapters", f"lora_{name}_a", jnp.zeros,
                     (pool, x.shape[-1], r), jnp.float32).value
    b = mdl.variable("adapters", f"lora_{name}_b", jnp.zeros,
                     (pool, r, fan_out), jnp.float32).value
    s = mdl.variable("adapters", f"lora_{name}_scale", jnp.zeros,
                     (pool,), jnp.float32).value
    xf = x.astype(jnp.float32)
    d = jnp.einsum("bsh,bhr->bsr", xf, a[idx])
    d = jnp.einsum("bsr,bro->bso", d, b[idx])
    return d * s[idx][:, None, None]


# The names a cache leaf that holds TOKENS may take: axis 1 (under the stacked
# layers) is the physical page where paged, the batch row in the slab. Whatever
# moves, shards or counts a cache by pages finds its leaves by these (page IO
# in inference/engine.py, inference/partition.py's specs,
# ``CausalLM.kv_cache_bytes``), so a leaf a configuration declares under one of
# them is carried by all of it: K and V heads, a latent (MLA) leaf under
# ``cached_key``, and the index keys a learned-sparse attention scores beside
# its latents (models/deepseek_v32.py). int8 pools bring per-(page, head)
# scales as sibling leaves.
INDEX_LEAF = "cached_index_key"
KV_PAGE_LEAVES = ("cached_key", "cached_value", INDEX_LEAF)
KV_SCALE_LEAVES = ("cached_key_scale", "cached_value_scale")


def leaf_paths(names) -> Tuple[str, ...]:
    """The names as ``jax.tree_util.keystr`` suffixes."""
    return tuple(f"['{name}']" for name in names)


def kv_leaf_shapes(cfg: LlamaConfig, batch: int) -> dict:
    """``{leaf name: (shape, dtype)}`` of ONE layer's KV storage in the
    ``cache`` collection: the paged pool ``(page_pool_pages, page_size, n_kv,
    hd)`` (int8 pages bring their per-(page, kv-head) fp32 scales as sibling
    leaves, n_kv at axis -2 like the pools, so partition specs, page-IO
    framing, handoff CRCs and donation cover them without special cases;
    all-zero scales dequantize unwritten pages to exact zeros), or the
    contiguous ``(batch, max_seq_len, n_kv, hd)`` slab. ``LlamaModel``
    declares each leaf once, stacked ``(num_layers, *shape)``.

    A configuration whose attention caches something else says so itself
    (``cfg.kv_leaf_shapes(batch)``; ``models/deepseek_v2.py``: ONE latent leaf
    under the name ``cached_key``, head axis 1, and no value leaf). Whatever
    finds pages by leaf name (page IO, partition specs, ``kv_cache_bytes``)
    goes by the names chosen here."""
    own = getattr(cfg, "kv_leaf_shapes", None)
    if own is not None:
        return own(batch)
    return kv_page_leaf_shapes(cfg, batch)


def kv_page_leaf_shapes(cfg: LlamaConfig, batch: int) -> dict:
    """:func:`kv_leaf_shapes` of a configuration that declares none of its
    own: K and V heads, paged or slab."""
    n_kv = cfg.num_kv_heads * cfg.kv_size_multiplier
    hd = cfg.head_dim_
    if not cfg.page_size:
        slab = ((batch, cfg.max_seq_len, n_kv, hd), cfg.dtype)
        return {"cached_key": slab, "cached_value": slab}
    npages = cfg.page_pool_pages
    if cfg.page_dtype == "int8":
        pool = ((npages, cfg.page_size, n_kv, hd), jnp.int8)
        scale = ((npages, 1, n_kv, 1), jnp.float32)
        return {"cached_key": pool, "cached_value": pool,
                "cached_key_scale": scale, "cached_value_scale": scale}
    pool = ((npages, cfg.page_size, n_kv, hd),
            jnp.dtype(cfg.page_dtype or cfg.dtype))
    return {"cached_key": pool, "cached_value": pool}


class KVLayerView:
    """One layer's window on the KV leaves, and the ONE place that says how
    the cache is threaded through the layer loop.

    The leaves are stacked ``(L, rows, ...)`` (rows: pages of the pool, batch
    rows of the slab) and are the layer scan's CARRY, not its scanned input
    and output: no layer ever holds a pool of its own, each writes its few
    rows of the one buffer in place and reads through the block table. A
    carry is also what the fused decode's step loop holds, so the argument
    the program was given (donated) is the buffer both loops update and the
    result it returns. Scanned (``variable_axes``), every layer would slice
    its pool out of the stack and write all of it back, and the step loop
    would copy the stack between the scan's input and output: time that
    follows the pages held, not the tokens live (PERF.md, PR 27).

    ``flat(name)`` is the leaf as ``(L * rows, ...)``, a free reshape in
    which this layer's rows start at ``first_row(rows)``: adding that to a
    block table's page ids (or to the slab's row ids) is all a layer does
    differently from owning its pool, and a write to be dropped goes past
    the end of the WHOLE stack, not of the layer's share (which is the next
    layer's first page)."""

    def __init__(self, layer: jax.Array, leaves: dict):
        self.layer = layer        # () int32: index of this layer in the stack
        self.leaves = dict(leaves)

    def first_row(self, rows: int) -> jax.Array:
        return self.layer * rows

    def flat(self, name: str) -> jax.Array:
        leaf = self.leaves[name]
        return leaf.reshape(leaf.shape[0] * leaf.shape[1], *leaf.shape[2:])

    def put(self, name: str, value: jax.Array) -> None:
        """Store the updated leaf (any reshape of it), pinned to its serving
        spec (n_kv over 'tp' under a mesh, no-op otherwise): row-axis
        scatters and gathers never cross the head shard, so the whole hot
        path stays local to a shard (inference/partition.py)."""
        from neuronx_distributed_tpu.inference.partition import constrain_named

        self.leaves[name] = constrain_named(
            name, value.reshape(self.leaves[name].shape))


class KVWalk:
    """What a ONE-TOKEN decode step reads of the cache: how far, of how many
    rows, and in what pieces.

    The cache of a row is ``max_seq_len`` slots, cut here into ``n_chunks``
    chunks of ``chunk`` tokens (``cut``): whole pages, an eighth of the table
    but not under 128 tokens, and a divisor of the table so that every chunk
    is full. A row's ``reach`` is ``cache_index + 1`` (its new token sits at
    slot ``cache_index``) if it is LIVE and 0 if not. ``live`` (b,) bool is
    what the serving program knows (the fused session decode: active and not
    done). A retired slot keeps a stale, still-growing ``cache_index`` over a
    table that points at scratch, a done row keeps counting: neither is read.
    Both bounds are values the program computes, not shapes, so one program
    serves every state of the batch:

    * how FAR: the chunks below ``extent``, the longest reach (``turns`` of
      them, at least one);
    * how MANY ROWS: the smallest of ``rungs`` (``ladder``) that holds the
      ``live_rows``. Below the top rung the rows are taken longest reach
      first (``sorted_rows``: the live rows come first), ``WalkRows.top(r)``
      picks the first ``r`` and ``WalkRows.back`` puts what was computed for
      them where the step expects it, zeros for every other row (nobody
      reads what a row that is not live computes). The top rung is the batch
      as it stands: nothing sorted, picked or put back.

    Without ``live`` (``lm.step``, ``generate``) every row counts: one rung,
    as far as the longest row. So does a table of one chunk.

    Two forms inside one program: ``fold`` (a loop with a traced trip count
    and a running softmax: no slab of keys or values is ever held, but a
    turn has a fixed cost and the accumulator rides every turn)
    and ``prefix`` (a ``lax.switch`` over the static prefixes of the table:
    no carried state and the one-pass softmax, at the price of a slab as long
    as the prefix). ``loops`` says which this walk takes: by the length of a
    chunk, from what the v5e showed (PERF.md, PR 38), unless the caller's
    cache cannot take the loop (``kv_walk``). The form sets the ladder too: a
    rung costs the program one loop, or an attention body for EVERY prefix.
    Either way the rung is chosen once a step: a rung chosen chunk by chunk
    inside the loop (each row read as far as ITS reach) pays a switch a turn,
    0.2-0.35 ms a step at Mistral-7B's 16 layers, more than it saves with
    three rows live or fewer (PERF.md, PR 40)."""

    # chunks of this many tokens or more (tables of 4 096 slots up) go by the
    # loop: Mistral-7B widths, 8 rows, 3 live near 1 850 of 4 096: 12.51 ms a
    # step by the loop, 12.91 by the switch, 15.68 whole; at 1 024 slots
    # (chunks of 128) the switch reads 10.92 / 6.76 ms against the loop's
    # 11.21 / 6.90 (Mistral / OLMoE; whole 11.38 / 7.69). The loop's fixed
    # cost a turn is what a short table cannot pay; the slab is what a long
    # one cannot
    LOOP_FROM = 512

    @staticmethod
    def cut(max_seq_len: int, page_size: Optional[int]) -> Tuple[int, int, int]:
        """``(pages a chunk, tokens a chunk, chunks)`` of a table."""
        page = page_size or 1               # the slab: "pages" of one token
        table_pages = max_seq_len // page
        pages = min(max(table_pages // 8, -(-128 // page)), table_pages)
        while table_pages % pages:
            pages += 1
        return pages, pages * page, table_pages // pages

    def ladder(self, b: int) -> Tuple[int, ...]:
        """The row counts a step of ``b`` rows may read. The loop form holds
        one loop a rung: 1, 2, 4, .. ``b``. The switch form holds an attention
        body for every (prefix, rung), and each costs set-up its trace and
        its compile: ONE rung below the top, a quarter of the batch. Where
        its chunks are long (a cache that cannot loop over a table of 4 096
        slots: the latent cache) no rung but the batch: a second set of
        bodies in DeepSeek-V2's two layer scans read 0.4-0.8 % off a token
        of ``deepseek-v2.longctx``, and cost 1.3-1.5 s of set-up with every
        compile cached (13 s cold) and 0.06 ms of every step with three
        rows live or more (PERF.md, PR 40)."""
        if self.loops:
            return (*(1 << i for i in range((b - 1).bit_length())), b)
        if self.chunk >= self.LOOP_FROM:
            return (b,)
        return tuple(sorted({max(b // 4, 1), b}))

    def __init__(self, max_seq_len: int, page_size: Optional[int], idx: jax.Array,
                 live: Optional[jax.Array] = None, loops: Optional[bool] = None):
        self.pages, self.chunk, self.n_chunks = self.cut(max_seq_len, page_size)
        self.loops = self.chunk >= self.LOOP_FROM if loops is None else loops
        self.idx = idx                      # (b,) cache_index BEFORE this step's write
        b = idx.shape[0]
        self.reach = jnp.minimum(idx + 1, max_seq_len)
        self.rungs = (b,)
        if live is not None:
            self.reach = jnp.where(live.reshape(idx.shape), self.reach, 0)
            if self.n_chunks > 1:
                self.rungs = self.ladder(b)
        self.extent = jnp.maximum(jnp.max(self.reach), 1)
        self.turns = (self.extent + self.chunk - 1) // self.chunk   # 1 .. n_chunks
        self.live_rows = jnp.sum(self.reach > 0)

    @property
    def tokens(self) -> jax.Array:
        """Slots the step reads of a row, chunk rounding included."""
        return self.turns * self.chunk

    def rung(self, rows) -> jax.Array:
        """Index of the smallest rung that holds ``rows`` rows."""
        return jnp.sum(rows > jnp.asarray(self.rungs[:-1], jnp.int32))

    @property
    def row_slots(self) -> jax.Array:
        """Slots the step reads, summed over the rows of its rung."""
        return jnp.asarray(self.rungs, jnp.int32)[self.rung(self.live_rows)] * self.tokens

    def sorted_rows(self) -> Tuple[jax.Array, jax.Array]:
        """``(order, place)``: the rows longest reach first, ties by row
        number, the rows without a reach last. ``order[k]`` is who stands at
        k and ``place[i]`` where row i stands. A stable sort of b numbers
        written as comparisons (no ``sort`` op in the layer body)."""
        row = jnp.arange(self.idx.shape[0])
        ahead = (self.reach[None] > self.reach[:, None]) | (
            (self.reach[None] == self.reach[:, None]) & (row[None] < row[:, None]))
        place = jnp.sum(ahead, axis=1)
        return jnp.sum((place[None] == row[:, None]) * row[None], axis=1), place

    def rows(self, q: jax.Array, table: Optional[jax.Array], first_row) -> "WalkRows":
        """The step's rows as the batch holds them: ``q`` (b, ...) the
        queries, ``table`` (b, table_pages) ids in the whole stack, or None
        for the slab, whose rows ``first_row .. + b`` these are."""
        return WalkRows(self, q, self.idx, table, None if table is not None else first_row)

    def fold(self, rows: "WalkRows", heads: Tuple[int, ...], dim: int, chunk_fn) -> jax.Array:
        """Softmax attention over the chunks below the bound, one chunk a
        turn of a loop whose trip count is ``turns``, over the rows of the
        rung (a ``lax.switch`` over the rungs, a loop in each):
        ``chunk_fn(top, c) -> (scores (r, *heads, chunk) float32,
        weigh)`` with ``weigh(probs) -> (r, *heads, dim) float32`` for the
        ``r`` rows of ``top``. Running max, sum and weighted values in
        float32; the sum is reassociated against a one-pass softmax and
        nothing else changes. Returns ``(b, *heads, dim)`` float32."""

        def loop(top):
            lead = (top.idx.shape[0], *heads)

            def turn(c, carry):
                m, l, acc = carry
                scores, weigh = chunk_fn(top, c)
                with jax.named_scope("attend"):
                    mask = top.visible(c, 1).reshape(lead[0], *(1,) * len(heads), self.chunk)
                    scores = jnp.where(mask, scores, -1e30)
                    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
                    p = jnp.exp(scores - m_new[..., None])
                    alpha = jnp.exp(m - m_new)
                    return (m_new, alpha * l + jnp.sum(p, axis=-1),
                            alpha[..., None] * acc + weigh(p))

            _, l, acc = jax.lax.fori_loop(
                0, self.turns, turn,
                (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
                 jnp.zeros((*lead, dim), jnp.float32)))
            return acc / l[..., None]

        return jax.lax.switch(self.rung(self.live_rows),
                              [functools.partial(rows.attend, r, loop) for r in self.rungs])

    def prefix(self, rows: "WalkRows", branch) -> jax.Array:
        """``branch(top, n)`` -> ``(r, ...)`` for the static ``n`` that
        equals ``turns`` and the ``r`` rows of the rung: ONE ``lax.switch``
        over the (prefix, rung) pairs, each whatever the caller does with
        ``top.span(.., 0, n)`` and ``top.visible(0, n)``, its sort and picks
        inside it. Returns ``(b, ...)``. (A switch over the rungs around one
        over the prefixes made the compiler re-lay-out the whole latent pool
        in every branch, 227 MB a layer-step at ``deepseek-v2.longctx``'s
        sizes: PERF.md, PR 40.)"""
        return jax.lax.switch(
            (self.turns - 1) * len(self.rungs) + self.rung(self.live_rows),
            [functools.partial(rows.attend, r, lambda top, n=n: branch(top, n))
             for n in range(1, self.n_chunks + 1) for r in self.rungs])


def kv_walk(cfg: LlamaConfig, idx: jax.Array, live: Optional[jax.Array] = None) -> KVWalk:
    """The walk of ``cfg``'s cache for a step whose rows hold ``idx`` tokens.
    A configuration whose cache cannot take the loop says so itself
    (``cfg.kv_walk_loops``; ``models/deepseek_v2.py``: the latent cache, by
    the switch whatever the table's length), as it says what its leaves are
    (:func:`kv_leaf_shapes`); whoever counts what a step read asks here too
    (``inference/causal_lm.py::_walk_sums``)."""
    return KVWalk(cfg.max_seq_len, cfg.page_size, idx, live,
                  getattr(cfg, "kv_walk_loops", None))


class WalkRows:
    """Rows of a one-token step as :class:`KVWalk` reads them (``KVWalk.rows``:
    all ``b`` as the batch holds them; ``top(r)``: the ``r`` of longest
    reach, and ``place``, where each row of the batch stands among the
    sorted): their queries ``q`` (an array, or a tree of arrays with the rows
    leading: what a step asks of a second leaf rides with them), their ``idx``
    (``cache_index`` before the
    write) and where their cache lies: ``table`` (r, table_pages), or for the
    slab ``slab``, the first row's id where the rows stand as the slab holds
    them and the ids (r,) of picked rows."""

    def __init__(self, walk: KVWalk, q, idx, table, slab, place=None):
        self.walk, self.q, self.idx, self.table, self.slab = walk, q, idx, table, slab
        self.place = place

    def top(self, r: int) -> "WalkRows":
        if r == self.idx.shape[0]:
            return self
        order, place = self.walk.sorted_rows()
        pick = order[:r]
        table, slab = ((self.table[pick], None) if self.slab is None
                       else (None, self.slab + pick))
        return WalkRows(self.walk, jax.tree.map(lambda a: a[pick], self.q), self.idx[pick],
                        table, slab, place)

    def attend(self, r: int, fn) -> jax.Array:
        """``fn(top) -> (r, ...)`` for the ``r`` rows of longest reach, as
        the ``(b, ...)`` the step expects. The sort and the picks happen
        HERE, inside whatever branch calls it: the top rung pays for none."""
        top = self.top(r)
        return top.back(fn(top))

    def back(self, got: jax.Array) -> jax.Array:
        """``got`` (r, ...), computed for these rows, as the ``(b, ...)`` the
        step expects: each row's at its own place, zeros for the rows
        outside the rung."""
        if self.place is None:
            return got
        rest = self.place.shape[0] - got.shape[0]
        return jnp.pad(got, [(0, rest)] + [(0, 0)] * (got.ndim - 1))[self.place]

    def span(self, flat: jax.Array, start, n: int) -> jax.Array:
        """``(r, n * pages, page, ...)``: chunks ``start .. start + n``
        (``start`` may be traced, ``n`` is static) of these rows, out of
        ``flat`` (``KVLayerView.flat``), through the table or out of the slab."""
        w = self.walk
        if self.table is not None:
            return flat[jax.lax.dynamic_slice_in_dim(
                self.table, start * w.pages, n * w.pages, axis=1)]
        if jnp.ndim(self.slab) == 0:
            rows = jax.lax.dynamic_slice_in_dim(flat, self.slab, self.idx.shape[0])
            return jax.lax.dynamic_slice_in_dim(
                rows, start * w.chunk, n * w.chunk, axis=1)[:, :, None]
        rest = flat.shape[2:]
        return jax.vmap(lambda row: jax.lax.dynamic_slice(
            flat, (row, start * w.chunk, *(0,) * len(rest)), (1, n * w.chunk, *rest))[0]
        )(self.slab)[:, :, None]

    def visible(self, start, n: int) -> jax.Array:
        """``(r, n * chunk)`` bool: key position ``j`` of those chunks is
        visible to row ``i`` iff ``j <= idx[i]`` (``cached_attention``'s mask
        for one new token)."""
        w = self.walk
        kpos = start * w.chunk + jnp.arange(n * w.chunk, dtype=jnp.int32)
        return kpos[None, :] <= self.idx[:, None]


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope,
                 kv: Optional[KVLayerView] = None, live=None) -> jax.Array:
        """``kv`` (decode only): this layer's view of the carried KV leaves.
        ``live`` (b, s) bool, where a serving program gives it: the rows a
        one-token step advances for someone (:class:`KVWalk`)."""
        cfg = self.config
        hd = cfg.head_dim_
        q, k, v = GQAQKVColumnParallelLinear(
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=hd,
            kv_size_multiplier=cfg.kv_size_multiplier,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="qkv",
        )(x)
        aidx = _adapter_idx(self, x.shape[0]) if cfg.lora_rank else None
        if aidx is not None and "qkv" in cfg.lora_targets:
            # per-row pooled corrections on the three fused projections,
            # applied pre-clip/pre-RoPE (the same point the training-path
            # attached adapters land, parallel/layers.py add_delta); K/V
            # deltas are computed COMPACT then head-repeated like the
            # kernels under kv_size_multiplier
            b, sq = x.shape[0], x.shape[1]
            q = q + _lora_pool_delta(self, cfg, "q", x, cfg.num_heads * hd,
                                     aidx).reshape(q.shape).astype(q.dtype)
            dk = _lora_pool_delta(self, cfg, "k", x, cfg.num_kv_heads * hd,
                                  aidx).reshape(b, sq, cfg.num_kv_heads, hd)
            dv = _lora_pool_delta(self, cfg, "v", x, cfg.num_kv_heads * hd,
                                  aidx).reshape(b, sq, cfg.num_kv_heads, hd)
            if cfg.kv_size_multiplier > 1:
                dk = jnp.repeat(dk, cfg.kv_size_multiplier, axis=2)
                dv = jnp.repeat(dv, cfg.kv_size_multiplier, axis=2)
            k = k + dk.astype(k.dtype)
            v = v + dv.astype(v.dtype)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = self._qk_norm("q_norm", q, cfg.num_heads, 1)
                k = self._qk_norm("k_norm", k, cfg.num_kv_heads, cfg.kv_size_multiplier)
        if cfg.qkv_clip is not None:  # DBRX clip_qkv (applied pre-RoPE)
            q = jnp.clip(q, -cfg.qkv_clip, cfg.qkv_clip)
            k = jnp.clip(k, -cfg.qkv_clip, cfg.qkv_clip)
            v = jnp.clip(v, -cfg.qkv_clip, cfg.qkv_clip)
        gate = self._head_gate(x) if cfg.attention_gate else None
        if cfg.decode:
            return self._decode_attention(x, q, k, v, kv, aidx, live, gate)
        if cfg.use_rope:
            cos, sin = rope  # computed once in LlamaModel, broadcast through scan
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        s = x.shape[1]
        if cfg.context_parallel:
            from neuronx_distributed_tpu.ops.ring_attention import ring_attention

            if cfg.attention_multiplier is not None:
                raise ValueError("ring attention scales by 1 / sqrt(head_dim) only")

            o = ring_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                layout=cfg.cp_layout,
                block_q=cfg.attention_block_q, block_k=cfg.attention_block_k,
            )
        else:
            from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

            blk_q, blk_k = cfg.blocks_for(s)
            # BSND -> BHSD for the kernel
            o = attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
                sm_scale=cfg.attention_multiplier,
                use_flash=cfg.use_flash_attention and flash_supported(s, s, blk_q, blk_k),
                block_q=blk_q,
                block_k=blk_k,
                **({"window": cfg.sliding_window} if cfg.sliding_window else {}),
            )
        o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], -1)
        return self._o_proj(o, aidx, gate)

    def _walk_attention(self, q, kv, walk: KVWalk, table):
        """One new token a row over what ``walk`` reads of the cache: the
        chunks below its bound, of the rows in its rung (``q`` (b, 1, n, hd)
        rotated, the cache already holds this step's K/V; ``table`` None for
        the slab). Grouped by KV head, K and V read in the cache's dtype,
        int8 pages dequantised a chunk at a time with that chunk's scales;
        scores, mask, softmax and accumulation float32, as
        :func:`cached_attention`."""
        cfg = self.config
        b, _, n, hd = q.shape
        n_kv = kv.leaves["cached_key"].shape[-2]
        rows = walk.rows(q, table, kv.first_row(b))
        exact = dict(preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)

        def read(top, name, start, count):
            with jax.named_scope("kv_gather"):
                pages = top.span(kv.flat(name), start, count)
                if cfg.page_dtype == "int8":
                    scales = top.span(kv.flat(name + "_scale"), start, count)
                    pages = (pages.astype(jnp.float32) * scales).astype(cfg.dtype)
                return pages.reshape(-1, count * walk.chunk, n_kv, hd)

        if not walk.loops:
            def branch(top, count):
                k_all = read(top, "cached_key", 0, count)
                v_all = read(top, "cached_value", 0, count)
                with jax.named_scope("attend"):
                    return cached_attention(top.q, k_all, v_all, top.idx,
                                            sm_scale=cfg.attention_multiplier)

            return walk.prefix(rows, branch)

        def chunk(top, c):
            k_c, v_c = read(top, "cached_key", c, 1), read(top, "cached_value", c, 1)
            with jax.named_scope("attend"):
                qg = top.q.reshape(-1, n_kv, n // n_kv, hd)
                scores = jnp.einsum("bkgd,bjkd->bkgj", qg, k_c, **exact) * (
                    cfg.attention_multiplier or 1.0 / hd ** 0.5)
            return scores, lambda p: jnp.einsum("bkgj,bjkd->bkgd", p, v_c, **exact)

        with jax.named_scope("attend"):
            o = walk.fold(rows, (n_kv, n // n_kv), hd, chunk)
            return o.reshape(b, 1, n, hd).astype(q.dtype)

    def _qk_norm(self, name, y, heads, repeat):
        """RMSNorm in float32 over the flattened (heads, head_dim) axes of
        ``y`` (b, s, heads * repeat, d): the mean of squares crosses the head
        shards under a TP mesh. The scale is stored flat, as published, and
        sharded as the heads are (compact under ``kv_size_multiplier``, like
        the K kernel: copies of a head leave the mean as it was)."""
        cfg = self.config
        hd = cfg.head_dim_
        scale = self.param(
            name, nn.with_partitioning(nn.initializers.ones_init(),
                                       (TP_AXIS if repeat == 1 else None,)),
            (heads * hd,), cfg.param_dtype).reshape(heads, hd)
        if repeat > 1:
            scale = jnp.repeat(scale, repeat, axis=0)
        yf = y.astype(jnp.float32)
        var = jnp.mean(jnp.square(yf), axis=(-2, -1), keepdims=True)
        y = (yf * jax.lax.rsqrt(var + cfg.rms_norm_eps)).astype(y.dtype)
        return y * scale.astype(y.dtype)

    def _head_gate(self, x):
        """``sigmoid(x W_g)`` (b, s, heads) float32, one scalar a head a
        token (``cfg.attention_gate``: the headwise form of gated attention)."""
        cfg = self.config
        if cfg.attention_gate != "per-head":
            raise ValueError(f"attention_gate {cfg.attention_gate!r}: 'per-head' or None")
        with jax.named_scope("attn_gate"):
            w = self.param("gate_kernel", nn.with_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"), (None, TP_AXIS)),
                (x.shape[-1], cfg.num_heads), cfg.param_dtype)
            return jax.nn.sigmoid((x.astype(cfg.dtype) @ w.astype(cfg.dtype)
                                   ).astype(jnp.float32))

    def _rotate_at(self, q, k, slots):
        """``q`` and ``k`` (b, s, n, hd) rotated to the positions ``slots``
        (b, s) of the cache they are written at."""
        cfg = self.config
        cos, sin = rotary_embedding(slots, cfg.rope_dims, cfg.rope_theta, dtype=q.dtype,
                                    scaling=cfg.rope_scaling)
        return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)

    def _o_proj(self, o, aidx=None, gate=None):
        """``o`` (b, s, heads * hd) through the output projection, each head
        first multiplied by its ``gate`` (b, s, heads) where there is one."""
        cfg = self.config
        if gate is not None:
            with jax.named_scope("attn_gate"):
                o = (o.reshape(*gate.shape, -1) * gate[..., None].astype(o.dtype)
                     ).reshape(o.shape)
        y = RowParallelLinear(
            cfg.hidden_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="o_proj",
        )(o)
        if aidx is not None and "o_proj" in cfg.lora_targets:
            y = y + _lora_pool_delta(self, cfg, "o_proj", o, cfg.hidden_size,
                                     aidx).astype(y.dtype)
        return y

    def _decode_attention(self, x, q, k, v, kv, aidx=None, live=None, gate=None):
        """KV-cached path (flax ``cache`` collection; the reference keeps KV
        state in aliased runtime buffers, model_base.py KV management —
        donation of the cache collection is the TPU analogue). The K/V
        leaves themselves come through ``kv`` (:class:`KVLayerView`): the
        stack of every layer's, carried by the layer loop.

        The new tokens' K/V are written first; what is then read depends on
        the step. One new token a row (the decode step): chunks of whole
        pages up to the reach of the longest LIVE row, of the rung of rows
        that holds the live ones (:class:`KVWalk`, ``_walk_attention``;
        ``live`` (b, s) is the serving program's, None counts every row; a
        row that is not live gets zeros where its rung leaves it out). A
        prompt or a chunk (``s_new > 1``) and any table of a single chunk:
        all ``max_seq_len`` slots behind the mask, as ever."""
        cfg = self.config
        b = x.shape[0]
        s_new = x.shape[1]
        n_kv = k.shape[2]
        hd = cfg.head_dim_
        ps = cfg.page_size
        if kv is None:
            raise ValueError(
                "decode-mode attention reads and writes the KV leaves that "
                "LlamaModel declares and its layer scan carries; apply it "
                "through LlamaModel (or pass a KVLayerView)")
        if cfg.sliding_window:
            raise ValueError(
                "a window layer's cache is a ring a slot (models/laguna.py); "
                "this attention would cache and read every position")
        if ps:
            # paged KV (PagedAttention layout, TPU-shaped): a page POOL
            # instead of a per-slot slab; per-slot block tables are a
            # cache-collection leaf, so the host swaps them between blocks
            # without touching any program signature and the K-step session
            # scan carries them as loop-invariant state (in-scan gather).
            # ``npages`` counts the pages of the whole stack and the table
            # below holds ids in it: from here on the code reads as if the
            # layer owned one pool of that size.
            npages = kv.flat("cached_key").shape[0]
            ppseq = cfg.max_seq_len // ps
            quantized = cfg.page_dtype == "int8"
            bt = self.variable("cache", "block_table",
                               lambda: jnp.zeros((b, ppseq), jnp.int32))
            table = bt.value + kv.first_row(cfg.page_pool_pages)   # (b, ppseq)
        # per-slot lengths: continuous batching reorders/restarts slots
        # independently (reference model_wrapper.py:207 seq_ids machinery)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((b,), jnp.int32))
        idx = ci.value                                            # (b,)
        # unified write: s_new tokens land at SLOTS idx..idx+s_new per slot —
        # covers prefill (idx=0), single-token decode (reference CTX/TKG
        # submodels + scatter_index, model_wrapper.py) AND chunked-prefill
        # extends (idx = tokens already written: a partial-length
        # continuation whose queries attend both the already-written prefix
        # and, causally, each other).
        #
        # Partial-length masking contract (what makes chunked prefill exact):
        # only positions < the row's TRUE length are ever visible — query i
        # sees key j iff j <= idx + i, and the serving layer resets
        # cache_index to the covered length after every chunk. A chunk's pad
        # tail (bucket width > real chunk tokens) therefore writes garbage
        # K/V only at slots STRICTLY ABOVE every real query position, where
        # it sits behind the mask exactly like the slab's unwritten zeros
        # until a later chunk / decode step overwrites it.
        slots = idx[:, None] + jnp.arange(s_new, dtype=jnp.int32)[None, :]
        rows = jnp.arange(b)[:, None]
        if cfg.use_rope:
            q, k = self._rotate_at(q, k, slots)
        with jax.named_scope("kv_write"):
            if ps:
                # write through the block table: logical slot -> physical page.
                # Writes at slots >= max_seq_len are DROPPED, matching the slab
                # path's out-of-bounds scatter (the overflow latch freezes a row
                # instead of letting its writes wrap onto a neighbour).
                if quantized:
                    # int8 pages: dequant-modify-requant over the W-page
                    # window this step touches (the narrowest logical span
                    # covering slots idx..idx+s_new-1 at any alignment).
                    # Absmax is a PAGE property, so inserting even one token
                    # re-derives the whole page's scale from its fp values.
                    W = (s_new + ps - 1) // ps + 1
                    first = idx // ps                                  # (b,)
                    lpage = (first[:, None]
                             + jnp.arange(W, dtype=jnp.int32)[None, :])  # (b, W)
                    from neuronx_distributed_tpu.inference.kv_quant import (
                        dequantize_kv_pages,
                        quantize_kv_pages,
                    )

                    phys_w = jnp.take_along_axis(
                        table, jnp.clip(lpage, 0, ppseq - 1), axis=1)  # (b, W)
                    kw = dequantize_kv_pages(kv.flat("cached_key")[phys_w],
                                             kv.flat("cached_key_scale")[phys_w])
                    vw = dequantize_kv_pages(kv.flat("cached_value")[phys_w],
                                             kv.flat("cached_value_scale")[phys_w])
                    kw = kw.reshape(b, W * ps, n_kv, hd)
                    vw = vw.reshape(b, W * ps, n_kv, hd)
                    # window-relative slots; >= max_seq_len drops like the fp
                    # scatter (overflow latch / chunk pad tails past the end)
                    rel = jnp.where(slots < cfg.max_seq_len,
                                    slots - first[:, None] * ps, W * ps)
                    kw = kw.at[rows, rel].set(k.astype(jnp.float32), mode="drop")
                    vw = vw.at[rows, rel].set(v.astype(jnp.float32), mode="drop")
                    # zero positions at/above the row's new length: stale
                    # bytes in a reused page are behind the mask for READS,
                    # but here they would inflate the fresh absmax scale
                    wpos = (first[:, None] * ps
                            + jnp.arange(W * ps, dtype=jnp.int32)[None, :])
                    live = (wpos < (idx + s_new)[:, None])[..., None, None]
                    kw = jnp.where(live, kw, 0.0).reshape(b, W, ps, n_kv, hd)
                    vw = jnp.where(live, vw, 0.0).reshape(b, W, ps, n_kv, hd)
                    # requantize: absmax per (page, kv head)
                    kq, k_sc = quantize_kv_pages(kw)
                    vq, v_sc = quantize_kv_pages(vw)
                    # write back ONLY pages this step actually touched: an
                    # untouched window page maps through table entries that
                    # may still be 0 — i.e. ANOTHER row's live physical page
                    # — so a blind window write-back would corrupt it.
                    last = jnp.minimum(idx + s_new - 1, cfg.max_seq_len - 1) // ps
                    touched = (lpage <= last[:, None]) & (lpage < ppseq)
                    dest = jnp.where(touched, phys_w, npages)          # (b, W)
                    for name, upd in (("cached_key", kq), ("cached_value", vq),
                                      ("cached_key_scale", k_sc),
                                      ("cached_value_scale", v_sc)):
                        kv.put(name, kv.flat(name).at[dest].set(upd, mode="drop"))
                else:
                    page_of = jnp.clip(slots // ps, 0, ppseq - 1)
                    phys = jnp.take_along_axis(table, page_of, axis=1)  # (b, s_new)
                    flat = jnp.where(slots < cfg.max_seq_len,
                                     phys * ps + slots % ps, npages * ps)
                    for name, upd in (("cached_key", k), ("cached_value", v)):
                        by_slot = kv.flat(name).reshape(npages * ps, n_kv, hd)
                        kv.put(name, by_slot.at[flat].set(
                            upd.astype(by_slot.dtype), mode="drop"))
            else:
                # mode="drop" pins the out-of-bounds semantics the overflow
                # latch and late chunked-prefill extends rely on (a chunk whose
                # pad tail runs past max_seq_len must discard those writes, not
                # clamp them onto the last slot) — this is jax's default for
                # scatters, made explicit so the contract can't drift
                first = kv.first_row(b)
                for name, upd in (("cached_key", k), ("cached_value", v)):
                    slab = kv.flat(name)            # (L * b, S, n_kv, hd)
                    kv.put(name, slab.at[first + rows, slots].set(
                        upd.astype(slab.dtype), mode="drop"))
                k_all, v_all = (
                    jax.lax.dynamic_slice_in_dim(kv.flat(name), first, b)
                    for name in ("cached_key", "cached_value"))
            ci.value = idx + s_new
        if s_new == 1:
            # the step reads its live rows as far as the longest reaches; a table
            # of ONE chunk (max_seq_len of 128 or less) is the whole read below
            walk = kv_walk(cfg, idx, None if live is None else live[:, 0])
            if walk.n_chunks > 1:
                o = self._walk_attention(q, kv, walk, table if ps else None)
                return self._o_proj(o.reshape(b, s_new, -1), aidx, gate)
        if ps:
            # in-scan gather: the (b, max_seq_len) logical view the
            # attention below consumes (prompts, chunks; a one-token step
            # left above). Stale bytes in reused pages sit behind the
            # position mask exactly like the slab's unwritten zeros (masked
            # scores are -1e30 -> exactly-zero probs), so attention over the
            # view is bit-identical to the contiguous path.
            with jax.named_scope("kv_gather"):
                # by whole pages, one (page, n_kv, hd) run of the buffer per
                # table entry: the same rows a gather by slot would bring
                k_all, v_all = (kv.flat(name)[table]          # (b, ppseq, ps, ..)
                                for name in ("cached_key", "cached_value"))
                if quantized:
                    # dequantize the logical view with each page's scale
                    k_all, v_all = (
                        (pages.astype(jnp.float32)
                         * kv.flat(name)[table]).astype(cfg.dtype)
                        for pages, name in ((k_all, "cached_key_scale"),
                                            (v_all, "cached_value_scale")))
                k_all, v_all = (pages.reshape(b, cfg.max_seq_len, n_kv, hd)
                                for pages in (k_all, v_all))
        # prefill/chunk attention: the Pallas kernel with per-slot position
        # masks (q at idx..idx+s_new; key j visible iff j <= q position, which
        # also excludes unwritten cache slots). The reference likewise uses
        # flash attention for prefill above a length threshold
        # (attention_base.py:103-114); short decode steps use the dense path.
        from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

        # block_k tiles the CACHE sweep (max_seq_len), not the query chunk
        cfg_blk_q, cfg_blk_k = cfg.blocks_for(s_new, cfg.max_seq_len)
        blk_q = min(cfg_blk_q, s_new)
        use_flash = (
            cfg.use_flash_attention
            and s_new >= 128
            and flash_supported(s_new, cfg.max_seq_len, blk_q, cfg_blk_k)
        )
        with jax.named_scope("attend"):
            if use_flash:
                o = attention(
                    q.transpose(0, 2, 1, 3),
                    k_all.transpose(0, 2, 1, 3),
                    v_all.transpose(0, 2, 1, 3),
                    causal=False,
                    sm_scale=cfg.attention_multiplier,
                    use_flash=True,
                    block_q=blk_q,
                    block_k=cfg_blk_k,
                    q_positions=slots,
                    kv_positions=None,  # default iota: j <= q position
                )
                o = o.transpose(0, 2, 1, 3)
            else:
                o = cached_attention(q, k_all, v_all, idx,
                                     sm_scale=cfg.attention_multiplier)
        o = o.reshape(b, s_new, -1)
        return self._o_proj(o, aidx, gate)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        aidx = _adapter_idx(self, x.shape[0]) if cfg.lora_rank else None
        gate = ColumnParallelLinear(
            cfg.intermediate_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="gate_proj",
        )(x)
        up = ColumnParallelLinear(
            cfg.intermediate_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="up_proj",
        )(x)
        if aidx is not None:
            if "gate_proj" in cfg.lora_targets:
                gate = gate + _lora_pool_delta(
                    self, cfg, "gate_proj", x, cfg.intermediate_size,
                    aidx).astype(gate.dtype)
            if "up_proj" in cfg.lora_targets:
                up = up + _lora_pool_delta(
                    self, cfg, "up_proj", x, cfg.intermediate_size,
                    aidx).astype(up.dtype)
        h = nn.silu(gate) * up
        y = RowParallelLinear(
            cfg.hidden_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="down_proj",
        )(h)
        if aidx is not None and "down_proj" in cfg.lora_targets:
            y = y + _lora_pool_delta(self, cfg, "down_proj", h,
                                     cfg.hidden_size, aidx).astype(y.dtype)
        return y


class LlamaDecoderLayer(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array, rope, kv=None, live=None) -> jax.Array:
        cfg = self.config
        h = cfg.make_norm(name="input_norm")(x)
        x = x + LlamaAttention(cfg, name="attention")(h, rope, kv, live)
        h = cfg.make_norm(name="post_attn_norm")(x)
        return x + LlamaMLP(cfg, name="mlp")(h)


def _remat_policy(name: Optional[str]):
    if name is None:
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "attention":
        # save the big matmul outputs, recompute elementwise — the selective
        # checkpoint choice of the reference at long seq (run_llama_nxd.py:113)
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"unknown remat policy {name!r}")


class _LayerStep(nn.Module):
    """Scan body: one (optionally remat-wrapped) decoder layer returning the
    ``(carry, ys)`` pair ``nn.scan`` expects. ``layer_cls`` parameterizes the
    decoder block so variants (Mixtral's MoE layer) reuse the whole stack.
    The carry is ``(x, kv)``: ``kv`` is None outside decode mode (nothing
    more is carried than the hidden states), else ``(layer index, KV
    leaves)``, which the block's attention updates through a
    :class:`KVLayerView`. ``live`` (b, s) bool, where a serving program gives
    it, says which tokens are real: a block's experts run those alone
    (``moe/layer.py``) and a one-token step's attention reads the cache of
    the live rows, as far as they reach (:class:`KVWalk`); ``stack`` is what
    ``LlamaModel.layer_stack`` hands every layer whole, to a block that asked
    for it."""

    config: LlamaConfig
    layer_cls: Any = None  # default LlamaDecoderLayer (set below)

    @nn.compact
    def __call__(self, carry, rope, live=None, stack=None):
        cfg = self.config
        x, kv = carry
        cls = self.layer_cls or LlamaDecoderLayer
        policy = _remat_policy(cfg.remat_policy)
        if policy is not None and kv is None:  # nothing differentiates a decode
            cls = nn.remat(cls, policy=policy, prevent_cse=False)
        block = cls(cfg, name="block")
        kwargs = {k: v for k, v in (("live", live), ("stack", stack))
                  if v is not None}
        if kv is None:
            return (block(x, rope, **kwargs), None), None
        layer, leaves = kv
        view = KVLayerView(layer, leaves)
        return (block(x, rope, kv=view, **kwargs),
                (layer + 1, view.leaves)), None


class LlamaModel(nn.Module):
    """Embedding + scanned decoder stack + final norm. Hidden states flow in
    ``(batch, seq, hidden)``; SP keeps seq sharded between attention/MLP."""

    config: LlamaConfig
    layer_cls: Any = None
    # block of the ``config.first_k_dense`` leading layers (DeepSeek's dense
    # layers before its expert layers); unused where the config has none
    dense_layer_cls: Any = None

    def setup(self):
        cfg = self.config
        self.embed = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, shard_over="vocab",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        )
        # scan over layers: one compiled body, params stacked on a leading
        # (unsharded) layer axis. "losses" carries per-layer sown aux losses
        # (MoE variants), "adapters" the per-layer LoRA pool stacks (multi-
        # LoRA serving), "moe_stats" the per-layer routing choices of a decode
        # step; unused collections in variable_axes are harmless. Of the
        # "cache" only the small per-layer leaves (cache_index, block_table)
        # are scanned: the K/V leaves are declared in __call__, above the
        # scan, and ride its carry (KVLayerView).
        def stack(length, layer_cls):
            return nn.scan(
                _LayerStep,
                variable_axes={"params": 0, "cache": 0, "losses": 0,
                               "adapters": 0, "moe_stats": 0},
                split_rngs={"params": True},
                length=length,
                in_axes=nn.broadcast,
                metadata_params={nn.meta.PARTITION_NAME: None},
            )(cfg, layer_cls)

        # leading layers of another kind are a scan of their own, BEFORE the
        # main one; the carry (layer index, K/V leaves) runs through both, so
        # they take rows 0..k-1 of the same stacked pool. Without them the
        # parameter tree and the programs are what they always were.
        first_k = getattr(cfg, "first_k_dense", 0)
        if first_k:
            self.dense_layers = stack(first_k, self.dense_layer_cls)
        self.layers = stack(cfg.num_layers - first_k, self.layer_cls)
        self.final_norm = cfg.make_norm()

    @nn.compact
    def __call__(self, input_ids: jax.Array, live=None) -> jax.Array:
        cfg = self.config
        if input_ids.shape[1] > cfg.max_seq_len:
            raise ValueError(
                f"sequence length {input_ids.shape[1]} exceeds max_seq_len {cfg.max_seq_len}"
            )
        x = self.embed(input_ids)
        if cfg.context_parallel and cfg.cp_layout == "zigzag":
            # tokens arrive zigzag-permuted (caller applied zigzag_indices);
            # position j of the permuted stream carries TRUE position idx[j]
            from neuronx_distributed_tpu.ops.ring_attention import zigzag_indices
            from neuronx_distributed_tpu.parallel import mesh as _ps
            from neuronx_distributed_tpu.parallel.mesh import CP_AXIS

            positions = zigzag_indices(
                input_ids.shape[1], _ps.get_mesh().shape[CP_AXIS])
        else:
            positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        # cos/sin computed ONCE here (not per scanned layer) and broadcast
        rope = rotary_embedding(positions, cfg.rope_dims, cfg.rope_theta,
                                dtype=x.dtype, scaling=cfg.rope_scaling)
        if cfg.context_parallel:
            if cfg.sequence_parallel:
                raise ValueError("sequence_parallel and context_parallel are exclusive")
            from neuronx_distributed_tpu.parallel.partitioning import ACT_CP

            x = constrain(x, ACT_CP)  # seq stays cp-sharded through the stack
        else:
            x = constrain(x, ACT_SP if cfg.sequence_parallel else ACT_FULL)
        kv, pools = None, {}
        if cfg.decode:
            # one buffer per K/V leaf for the whole stack, in and out of the
            # layer loop as its carry: written in place at [layer, row]
            # (``cfg.kv_layers``: a layer that holds more than one attention
            # says how many cache layers the stack has; models/longcat_flash.py)
            pools = {
                name: self.variable("cache", name, jnp.zeros,
                                    (getattr(cfg, "kv_layers", cfg.num_layers), *shape), dtype)
                for name, (shape, dtype) in kv_leaf_shapes(
                    cfg, input_ids.shape[0]).items()}
            kv = (jnp.int32(0), {n: p.value for n, p in pools.items()})
        stack = self.layer_stack() if cfg.decode else None
        args = (rope, live, stack)
        while args[-1] is None:     # dense models, training: (rope,) as ever
            args = args[:-1]
        # a config with ``hc_mult`` carries that many residual streams, each (b,
        # s, hidden), from here to the final norm (models/xing4.py); the scans
        # and ``_LayerStep`` carry whatever they are given
        streams = getattr(cfg, "hc_mult", None)
        if streams:
            x = mhc_expand(x, streams)
        carry = (x, kv)
        if getattr(cfg, "first_k_dense", 0):
            # a one-token step's attention wants `live` (KVWalk); a prompt's
            # does not, and a dense block has no other use for it
            # (one that scores the cached tokens asks which of a prompt's
            # queries are real: ``cfg.prompt_live``, models/deepseek_v32.py)
            dense = (rope, live if input_ids.shape[1] == 1
                     or getattr(cfg, "prompt_live", False) else None)
            while dense[-1] is None:
                dense = dense[:-1]
            carry, _ = self.dense_layers(carry, *dense)
        (x, kv), _ = self.layers(carry, *args)
        for name, pool in pools.items():
            pool.value = kv[1][name]
        if streams:
            x = mhc_reduce(x)
        return self.final_norm(x)

    def layer_stack(self):
        """Parameters of ALL layers that the block class wants whole in every
        layer, beside the layer's own slice of them (its ``layer_stack``
        picks them out of the stacked block parameters; ``MixtralDecoderLayer``:
        the experts' weights, for a kernel that indexes ``[layer, expert]``).
        None: nothing, as for every dense block."""
        pick = getattr(self.layer_cls, "layer_stack", None)
        if pick is None:
            return None
        params = nn.meta.unbox(self.layers.variables.get("params", {}))
        return pick(params.get("block", {}))

    def attend(self, x: jax.Array) -> jax.Array:
        """Tied-embedding logits (``tie_word_embeddings``)."""
        return self.embed.attend(x)


class LlamaForCausalLM(nn.Module):
    """Model + vocab-parallel LM head (tied to the embedding when
    ``config.tie_word_embeddings``). ``__call__`` returns (vocab-sharded)
    logits; ``loss`` computes the vocab-parallel CE without materializing
    gathered logits (reference ``parallel_cross_entropy`` wiring) — and at
    long sequence, without materializing full-sequence logits at all: the
    head matmul + CE run per sequence chunk under ``jax.checkpoint``, so
    live logits are one chunk's (the (S, vocab) fp32 logit+grad buffers are
    what OOM a 32k-seq step; the reference leans on Neuron runtime memory
    there, SURVEY §5.7 memory levers)."""

    config: LlamaConfig
    layer_cls: Any = None  # decoder-block override (e.g. Mixtral's MoE layer)
    dense_layer_cls: Any = None  # block of ``config.first_k_dense`` leading layers

    def setup(self):
        cfg = self.config
        self.model = LlamaModel(cfg, self.layer_cls, self.dense_layer_cls)
        if not cfg.tie_word_embeddings:
            # logits matmul runs in the compute dtype (bf16 MXU rate); the
            # vocab-parallel CE upcasts to fp32 for the softmax/LSE math
            # (parallel/loss.py) — fp32 here would force a slow fp32 matmul
            # and materialize 4-byte logits for no numerical benefit
            self.lm_head = ColumnParallelLinear(
                cfg.vocab_size, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            )

    def _head(self, x: jax.Array) -> jax.Array:
        if self.config.tie_word_embeddings:
            return self.model.attend(x)
        return self.lm_head(x)

    def _hidden(self, input_ids: jax.Array, live=None) -> jax.Array:
        x = self.model(input_ids, **({} if live is None else {"live": live}))
        if self.config.sequence_parallel:
            x = constrain(x, ACT_FULL)
        return x

    def __call__(self, input_ids: jax.Array, live=None) -> jax.Array:
        """``live`` (b, s) bool: which tokens are real, where a serving
        program knows (``_LayerStep``); None counts every token."""
        return self._head(self._hidden(input_ids, live))

    def last_logits(self, input_ids: jax.Array, last: jax.Array,
                    live=None) -> jax.Array:
        """``(b, vocab)`` logits of ONE position a row, ``last`` (b,): the
        head runs over those ``b`` hidden states only. What a serving insert
        reads of a prompt (``CausalLM._first_token``); the head over the
        other positions is work nobody reads."""
        x = self._hidden(input_ids, live)
        return self._head(x[jnp.arange(x.shape[0]), last][:, None])[:, 0]

    def loss(self, input_ids: jax.Array, labels: jax.Array,
             ignore_index: int = -100) -> jax.Array:
        cfg = self.config
        x = self._hidden(input_ids)
        b, s = labels.shape
        chunk = cfg.loss_chunk_size or 4096
        if s <= chunk or cfg.context_parallel:
            # under CP the tokens are already cp-sharded — per-chip logits are
            # S/cp-sized and slicing the sharded dim would force resharding
            return parallel_cross_entropy_mean(self._head(x), labels,
                                               ignore_index=ignore_index)
        # chunked head+CE: per chunk, remat recomputes the head matmul and
        # softmax internals in backward, so only the chunk's logits are ever
        # live (unrolled python loop — chunk count is small and static;
        # nn.remat is the lifted form flax requires for submodule calls
        # under checkpoint). A non-dividing seq gets a final short chunk —
        # falling back to the whole-seq path would re-create the very OOM
        # this exists to remove.

        def chunk_loss(mdl, xc, lc):
            per_tok = parallel_cross_entropy(mdl._head(xc), lc,
                                             ignore_index=ignore_index)
            cnt = jnp.sum((lc != ignore_index).astype(jnp.float32))
            return jnp.sum(per_tok), cnt

        chunk_loss = nn.remat(chunk_loss,
                              policy=jax.checkpoint_policies.nothing_saveable,
                              prevent_cse=False)
        total = jnp.zeros((), jnp.float32)
        count = jnp.zeros((), jnp.float32)
        for i in range(0, s, chunk):
            sl, cn = chunk_loss(self, x[:, i:i + chunk], labels[:, i:i + chunk])
            total, count = total + sl, count + cn
        return total / jnp.maximum(count, 1.0)
