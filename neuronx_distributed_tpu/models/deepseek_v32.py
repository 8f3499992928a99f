"""DeepSeek-V3.2 (``model_type: deepseek_v32``): DeepSeek-V2's latent attention
with a lightning indexer beside it (DeepSeek Sparse Attention), and V3's route.

What is this file's and what is ``models/deepseek_v2.py``'s:

* **The attention** is :class:`DeepseekV2Attention` (the five MLA matrices, the
  latent leaf, YaRN, the expanded prompt and the absorbed one-token step stay
  one code) with its indexer seam filled. ``h_t`` the layer's normed input,
  ``c^Q_t`` MLA's normed query latent:

      q^I_{t,j} = (c^Q_t W^I_q)_j          j = 1..index_n_heads, index_head_dim wide
      k^I_s     = LayerNorm(h_s W^I_k)     ONE key a token, shared by the heads
      w_{t,j}   = (h_t W^I_w)_j * index_n_heads^-1/2 * index_head_dim^-1/2
      I_{t,s}   = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)         s <= t
      S_t       = the index_topk largest I_{t,s} (a tie to the lower position)
      o_t       = latent attention of token t over the tokens of S_t ONLY

  rotary (the layer's own tables) on the first ``qk_rope_head_dim`` dims of
  ``q^I`` and ``k^I``. While a token sees no more than ``index_topk`` tokens
  the choice is all of them, which is DeepSeek-V2's attention.
* **A second cache leaf**, ``cached_index_key``: ``(pages, page_size, 1,
  index_head_dim)`` in the pages' dtype, declared by the config beside the
  latent leaf, written at the same slots through the same block table. Page IO,
  partition specs, the byte counts and prefix sharing find it by its name
  (``models/llama.py::KV_PAGE_LEAVES``): a shared page shares both leaves.
* **One new token** scores the index keys of the walk's extent (float32
  accumulation), finds each row's ``index_topk``-th largest score and reads the
  latent extent under the mask of the chosen (``dsa_select``); a prefix of the
  table no longer than ``index_topk`` holds no choice and traces DeepSeek-V2's
  branch plus nothing. The latents are NOT gathered: the step reads what
  DeepSeek-V2's reads, and the counters say so (``sparse_walk_sums``).
* **A prompt** runs the flash kernel over its first ``index_topk`` new tokens
  and the slots below ``index_topk`` (exact wherever their positions stay
  below ``index_topk``: such a query sees no slot past it), then blocks of
  ``index_block_q`` queries under ``lax.map``: a block whose real queries all
  lie below ``index_topk`` (or that holds none) keeps the kernel's result, any
  other scores, chooses and attends under its own mask over the prefix of the
  cache its last query reaches (a ``lax.switch`` over the walk's chunks). A
  row's choices are made first (``(s, extent)`` bool), then its attention a
  GROUP of heads at a time (keys and values brought up, the kernel, the masked
  blocks), so that scores of ``(group, block, prefix)`` and one group's keys
  and values are all that is ever alive (``SCORES_BYTES``).
* **The route** is ``moe/routing.py::RouterTopK`` with sigmoid scores, the
  selection bias inside groups and ``group_score="top2_sum"`` (``noaux_tc``).

Not here: the multi-token-prediction module (``num_nextn_predict_layers``: a
training objective and an optional draft head; serving yields one token a row
a step), FP8 index keys and the Hadamard rotation of ``q^I`` / ``k^I``
(orthogonal: the dot products are the same in exact arithmetic), ``tp > 1``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2Attention,
    DeepseekV2Config,
    DeepseekV2DenseLayer,
    DeepseekV2MoELayer,
)
from neuronx_distributed_tpu.models.llama import (
    INDEX_LEAF,
    KVWalk,
    LlamaForCausalLM,
    YarnScaling,
    apply_rotary,
)


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(DeepseekV2Config):
    # None: no indexer, no second leaf: DeepSeek-V2's attention under V3's route
    index_topk: Optional[int] = 2048
    index_n_heads: int = 64
    index_head_dim: int = 128
    # queries a block of a prompt's masked attention (the bucket if it does
    # not divide it): scores of (heads, block, prefix) float32 are alive
    index_block_q: int = 128
    # DeepSeek-V3's layer
    first_k_dense: int = 3
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    num_experts: int = 256
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    router_selection_bias: bool = True
    group_score: str = "top2_sum"

    def __post_init__(self):
        super().__post_init__()
        if self.index_topk is None:
            return
        if self.index_topk < 1 or self.index_n_heads < 1:
            raise ValueError(f"index_topk {self.index_topk}, index_n_heads {self.index_n_heads}")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"{INDEX_LEAF}: an index key of {self.index_head_dim} dims cannot take the "
                f"rotary of {self.qk_rope_head_dim}")

    @property
    def prompt_live(self) -> bool:
        """A prompt's attention asks which of its queries are real, in the
        leading dense layers too (``LlamaModel``): a block of queries that
        holds none scores and chooses nothing."""
        return self.index_topk is not None

    def kv_leaf_shapes(self, batch: int) -> dict:
        """The latent leaf and, with an indexer, the index keys beside it: the
        same pages (or slab rows), one key of ``index_head_dim`` a token."""
        leaves = super().kv_leaf_shapes(batch)
        if self.index_topk is not None:
            (shape, dtype), = leaves.values()
            leaves[INDEX_LEAF] = ((*shape[:-1], self.index_head_dim), dtype)
        return leaves

    def sparse_walk_sums(self, walk: KVWalk):
        """Of ONE decode step, summed over its sparse layers (all of them):
        the tokens its live rows saw, the tokens chosen for them
        (``min(reach, index_topk)`` a row) and the latent slots the step read
        (the rows of its rung as far as the walk goes: the chosen are read
        under a mask, not gathered). ``inference/causal_lm.py::_walk_sums``."""
        return (self.num_layers * jnp.sum(walk.reach),
                self.num_layers * jnp.sum(jnp.minimum(walk.reach, self.index_topk)),
                self.num_layers * walk.row_slots)


def deepseek_v32(**over) -> DeepseekV32Config:
    """deepseek-ai/DeepSeek-V3.2: 671 B parameters, 37 B active."""
    return DeepseekV32Config(**{**dict(
        vocab_size=129280, hidden_size=7168, intermediate_size=18432, num_layers=61,
        num_heads=128, num_kv_heads=128, rope_theta=10000.0, max_seq_len=4096,
        rope_scaling=YarnScaling(
            factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    ), **over})


# what a block of a prompt's masked attention may hold of float32 scores at once;
# the heads go in groups that stay under it. 32 MiB (16 heads of 128 queries over
# 4096 slots) is what the v5e's compiler keeps in VMEM from the first product to the
# second; at 128 MiB (64 heads) the scores crossed HBM three times a block, and one
# row's insert of 3000 tokens took 249 ms where it now takes 196 (64 MiB 201, 16 MiB
# 197; PERF.md section 6, PR 56)
SCORES_BYTES = 32 * 2 ** 20


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I = sum_j w_j relu(q_j . k)`` in float32: ``q`` (r, j, d) and ``w``
    (r, j) float32 of ``r`` queries over ``keys`` (t, d), all queries' alike,
    or (r, t, d), a query's own; returns (r, t)."""
    own = "r" if keys.ndim == 3 else ""
    dots = jnp.einsum(f"rjd,{own}td->rjt", q.astype(keys.dtype), keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("rjt,rj->rt", jax.nn.relu(dots), w)


def _ordered_bits(scores: jax.Array) -> jax.Array:
    """float32 scores as int32 keys in the same order (``a < b`` iff ``key(a) <
    key(b)``; -0.0 counts as 0.0, as the floats compare)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """``(r, 1)`` int32: the ``k``-th largest of each row of ``keys`` (r, t),
    by bisection on its 32 bits: the largest value that ``k`` keys reach. 32
    counts of a row; on the v5e 16-25 us where ``lax.top_k(.., 2048)``, a sort
    there, takes 19-44 us for 8 rows of 3072-8192 and 160-200 for 128 rows
    (PERF.md section 6, PR 56)."""
    def narrow(i, low):
        # the sign first (from the least int32 upward), then bit 30 down to 0
        mid = jnp.where(i == 0, jnp.zeros_like(low), low | (jnp.int32(1) << (31 - i)))
        return jnp.where(jnp.sum(keys >= mid, axis=-1, keepdims=True) >= k, mid, low)

    least = jnp.full((keys.shape[0], 1), jnp.iinfo(jnp.int32).min, jnp.int32)
    return jax.lax.fori_loop(0, 32, narrow, least)


def choose_topk(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """``(r, t)`` bool: of each row's ``visible`` slots the ``k`` of largest
    score, every visible one where there are no more than ``k``; a tie at the
    ``k``-th place goes to the lower slots, as ``lax.top_k`` breaks it. By the
    ``k``-th largest score as a threshold: no index is scattered or gathered."""
    if scores.shape[-1] <= k:
        return visible
    keys = _ordered_bits(jnp.where(visible, scores.astype(jnp.float32), -jnp.inf))
    kth = kth_largest_key(keys, k)
    above = keys > kth
    ties = (keys == kth) & visible
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & visible


class DeepseekV32Attention(DeepseekV2Attention):
    """:class:`DeepseekV2Attention` with the indexer seam filled."""

    config: DeepseekV32Config

    def _index(self, x, c_q, w_uq, kernel):
        """What the indexer makes of the new tokens, before the rotary: the key
        ``k`` (b, s, 1, d) and the weights ``w`` (b, s, heads) float32; the
        queries stay latent (``c_q`` with ``w_q``, and MLA's ``w_uq``) until a
        row or a step asks (``_index_rotated``, ``_chosen_row``)."""
        cfg = self.config
        if cfg.index_topk is None:
            return None
        nh, hd = cfg.index_n_heads, cfg.index_head_dim
        with jax.named_scope("dsa_index"):
            w_q = kernel("index_q_proj", (cfg.q_lora_rank, nh, hd), (None, None, None),
                         cfg.q_lora_scale)
            w_k = kernel("index_k_proj", (cfg.hidden_size, hd), (None, None))
            w_w = kernel("index_weights_proj", (cfg.hidden_size, nh), (None, None))
            key = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype, name="index_k_norm")(x @ w_k)
            return {"c_q": c_q, "w_q": w_q, "w_uq": w_uq, "k": key[:, :, None],
                    "w": (x @ w_w).astype(jnp.float32) * (nh ** -0.5 * hd ** -0.5)}

    def _index_rotated(self, index, cos, sin):
        """The key rotated; of one new token a row also its index queries (what
        rides with the step's rows: arrays with the rows leading, all of them);
        a prompt's rows bring their queries up themselves and keep the tables."""
        with jax.named_scope("dsa_index"):
            key = apply_rotary(index["k"], cos, sin)
            if key.shape[1] > 1:
                b, s = key.shape[:2]
                return dict(index, k=key, cos=jnp.broadcast_to(cos, (b, s, cos.shape[-1])),
                            sin=jnp.broadcast_to(sin, (b, s, sin.shape[-1])))
            q = jnp.einsum("bsr,rjd->bsjd", index["c_q"], index["w_q"])
            return {"q": apply_rotary(q, cos, sin), "k": key, "w": index["w"]}

    def _index_write(self, kv, index, at):
        """The new tokens' index keys into their leaf, at the latents' slots."""
        leaf = kv.flat(INDEX_LEAF)
        kv.put(INDEX_LEAF, at(leaf).set(index["k"].astype(leaf.dtype), mode="drop"))

    def _chosen(self, asks, kv, top, count):
        """``(r, count x chunk)`` bool for a one-token step: of what is visible
        to each row of ``top`` in the first ``count`` chunks, the tokens its
        indexer (``asks``, the rows' own) chose."""
        cfg = self.config
        tokens = count * top.walk.chunk
        visible = top.visible(0, count)
        if tokens <= cfg.index_topk:      # no choice to make: every visible token
            return visible
        with jax.named_scope("kv_gather"):
            keys = top.span(kv.flat(INDEX_LEAF), 0, count).reshape(
                -1, tokens, cfg.index_head_dim)
        with jax.named_scope("dsa_scores"):
            scores = index_scores(asks["q"][:, 0], asks["w"][:, 0], keys)
        with jax.named_scope("dsa_select"):
            return choose_topk(scores, visible, cfg.index_topk)

    def _chosen_prompt(self, q, slab, slots, index, live, cache, w_uk, w_uv):
        """A prompt's rows, one at a time, at ``slots`` (b, s) over the latents
        ``slab`` (b, S, latent) of slots 0..S-1; ``cache`` is ``(kv, table)``
        where the index keys of those slots lie in their leaf, None where the
        new tokens' own are all there are (no cache: ``S == s``). ``live``
        (b, s) bool or None: the real queries. ``q``, every row's queries at
        once, is NOT read: a row brings its own up from ``c_q`` (at 8 x 4096
        tokens all rows' are 1.5 GB, and the indexer's 0.5 GB more)."""
        cfg = self.config
        b, s = slots.shape
        S = slab.shape[1]
        if cache is None:
            keys, chunk = index["k"][:, :, 0], S
        else:
            kv, table = cache
            with jax.named_scope("kv_gather"):
                leaf = kv.flat(INDEX_LEAF)
                rows = (leaf[table] if table is not None
                        else jax.lax.dynamic_slice_in_dim(leaf, kv.first_row(b), b))
                keys = rows.reshape(b, S, cfg.index_head_dim)
            chunk = KVWalk.cut(S, cfg.page_size)[1]
        if live is None:
            live = jnp.ones((b, s), bool)
        weights = (index["w_uq"], index["w_q"], w_uk, w_uv)
        args = (slab, keys, slots, live, index["c_q"], index["w"], index["cos"], index["sin"])

        def row(args):
            # as far as the row's last real query reaches, by halves of the table:
            # keys and values come up (and the kernel sweeps) over that alone
            last = jnp.max(jnp.where(args[3], args[2], -1))
            half = S // 2
            if half % chunk or half <= cfg.index_topk:
                return self._chosen_row(args, S, chunk, weights)
            return jax.lax.cond(last < half,
                                lambda: self._chosen_row(args, half, chunk, weights),
                                lambda: self._chosen_row(args, S, chunk, weights))

        if b == 1:
            return row(tuple(a[0] for a in args))[None]
        return jax.lax.map(row, args)

    def _chosen_row(self, args, extent, chunk, weights):
        """ONE row of a prompt over slots 0..extent-1: returns (s, n, v).
        First every block's choice (``(s, extent)`` bool: 32 MB at 4096 x
        8192), then the attention a GROUP of heads at a time (their keys and
        values come up, the kernel runs and the masked blocks attend inside
        the group), so that what is alive follows the group, not the heads."""
        slab, keys, pos, live, c_q, iw, cos, sin = args
        w_uq, w_q, w_uk, w_uv = weights
        cfg = self.config
        topk, vd, nope, n = cfg.index_topk, cfg.v_head_dim, cfg.qk_nope_head_dim, cfg.num_heads
        s = pos.shape[0]
        slab, keys = slab[:extent], keys[:extent]
        # the kernel over the new tokens that can lie below index_topk: new
        # token i sits at cache_index + i >= i
        head = min(s, topk)
        bq = cfg.index_block_q if s % cfg.index_block_q == 0 else s
        first = topk // chunk + 1           # the shortest prefix that holds a choice
        # no slot past index_topk: nothing to choose, the kernel's result stands
        prefixes = [m * chunk for m in range(first, extent // chunk + 1)] if extent > topk else []
        below = -(-topk // chunk) * chunk if prefixes else extent   # slots 0..index_topk-1, by chunks
        group = max(g for g in range(1, n + 1)
                    if n % g == 0 and (g == 1 or 4 * g * bq * extent <= SCORES_BYTES))
        blocks = lambda *arrays: jax.tree.map(  # noqa: E731
            lambda a: a.reshape(s // bq, bq, *a.shape[1:]), arrays)

        def choice(block):
            """``(case, (bq, extent) bool)`` of a block: which prefix it reads
            (0: the kernel's result stands) and what its queries chose there."""
            posb, liveb, c_qb, iwb, cosb, sinb = block
            last = jnp.max(jnp.where(liveb, posb, -1))      # the furthest real query
            m = jnp.clip((last + chunk) // chunk, first, first + len(prefixes) - 1)

            def chosen(reach):
                with jax.named_scope("dsa_index"):
                    iqb = apply_rotary(jnp.einsum("sr,rjd->sjd", c_qb, w_q)[None],
                                       cosb[None], sinb[None])[0]
                with jax.named_scope("dsa_scores"):
                    scores = index_scores(iqb, iwb, keys[:reach])
                with jax.named_scope("dsa_select"):
                    visible = jnp.arange(reach, dtype=jnp.int32)[None] <= posb[:, None]
                    return jnp.pad(choose_topk(scores, visible, topk),
                                   ((0, 0), (0, extent - reach)))

            case = jnp.where(last >= topk, m - first + 1, 0)
            return case, jax.lax.switch(
                case, [lambda: jnp.zeros((bq, extent), bool)]
                + [functools.partial(chosen, reach) for reach in prefixes])

        if prefixes:
            cases, masks = jax.lax.map(choice, blocks(pos, live, c_q, iw, cos, sin))

        def heads(w):
            w_q_g, w_uk_g, w_uv_g = w           # this group's columns of W_uq, W_uk, W_uv
            with jax.named_scope("mla_q"):
                q = jnp.einsum("sr,rnd->snd", c_q, w_q_g)
                q = jnp.concatenate([q[..., :nope], apply_rotary(
                    q[None, ..., nope:], cos[None], sin[None])[0]], axis=-1)
            k, v = self._kv_up(slab, w_uk_g, w_uv_g, heads_first=True)      # (group, extent, ..)
            # the kernel's result stands only for queries below index_topk, and
            # those see no slot past it: the kernel sweeps the chunks that hold them
            dense = self._causal(q[:head], k[:, :below], v[:, :below], pos[:head],
                                 heads_first=True)
            if not prefixes:
                return dense
            dense = jnp.pad(dense, ((0, s - head), (0, 0), (0, 0)))

            def one(block):
                case, mask, qb = block

                def masked(reach):
                    # the softmax's sum divides the (block, head) outputs, not the
                    # (head, block, reach) weights: one pass over the scores fewer,
                    # and the weights leave their exp in the values' dtype (as the
                    # kernel multiplies them)
                    with jax.named_scope("attend"):
                        logits = jnp.where(
                            mask[None, :, :reach],
                            jnp.einsum("qnd,nkd->nqk", qb, k[:, :reach],
                                       preferred_element_type=jnp.float32) * cfg.softmax_scale,
                            -1e30)
                        weights = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
                        total = jnp.sum(weights, axis=-1)                    # (group, bq)
                        out = jnp.einsum("nqk,nkd->qnd", weights.astype(v.dtype), v[:, :reach],
                                         preferred_element_type=jnp.float32)
                        return (out / total.T[:, :, None]).astype(q.dtype)

                return jax.lax.switch(case, [lambda: jnp.zeros((bq, group, vd), q.dtype)] + [
                    functools.partial(masked, reach) for reach in prefixes])

            # the kernel's blocks are chosen AFTER the loop, in one pass: a branch that
            # hands the kernel's block through makes the loop carry the kernel's whole
            # result, and the chip's compile copied it (67 MB) every time that branch ran
            sparse = jax.lax.map(one, (cases, masks, *blocks(q)))
            kept = (cases == 0)[:, None, None, None]
            return jnp.where(kept, *blocks(dense.astype(q.dtype)), sparse).reshape(s, group, vd)

        if group == n:
            return heads((w_uq, w_uk, w_uv))
        split = lambda w: w.reshape(w.shape[0], n // group, group, w.shape[2]).transpose(1, 0, 2, 3)  # noqa: E731
        o = jax.lax.map(heads, (split(w_uq), split(w_uk), split(w_uv)))     # (groups, s, group, v)
        return o.transpose(1, 0, 2, 3).reshape(s, n, vd)


class DeepseekV32DenseLayer(DeepseekV2DenseLayer):
    attention_cls = DeepseekV32Attention


class DeepseekV32MoELayer(DeepseekV2MoELayer):
    attention_cls = DeepseekV32Attention


class DeepseekV32ForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM`` over DeepSeek-V3.2's two kinds of layer."""

    layer_cls: Any = DeepseekV32MoELayer
    dense_layer_cls: Any = DeepseekV32DenseLayer
