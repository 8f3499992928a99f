"""DeepSeek-V2 model family (``model_type: deepseek_v2``): latent attention
(MLA) over the Llama stack, leading dense layers before the expert layers,
group-limited routing with shared experts, and an expert layer that may hold
only this chip's share of the routed experts.

What is this file's and what is the stack's:

* **Latent attention** (:class:`DeepseekV2Attention`). Queries go down to
  ``q_lora_rank``, through an RMSNorm and up to heads of
  ``[q_nope | q_rope]``; keys and values go down to ONE latent of
  ``kv_lora_rank`` a token (RMSNorm'ed) beside ONE rotary key of
  ``qk_rope_head_dim`` shared by all heads. The cache holds that pair and
  nothing else: one leaf, ``(pages, page_size, 1, kv_lora_rank +
  qk_rope_head_dim)``, declared by the CONFIG (``kv_leaf_shapes``) under the
  name ``cached_key`` so that page IO, partition specs and byte counts find it
  as they find every model's pages (``models/llama.py::kv_leaf_shapes``).
  A prompt (more than one new token) attends in the expanded form: keys and
  values come up through ``k_b_proj`` and ``v_b_proj`` (the published
  ``kv_b_proj``, kept as its two halves) and through the flash kernel. How
  far a row reads is decided inside the program by the call's
  ``cache_index``: where every row starts at 0 (an insert without a prefix
  hit) a query can see the call's own tokens alone, so slots ``0..s_new-1``
  are expanded and swept, the rows as the kernel's batch in one call where its
  arrays stay under ``PROMPT_CALL_BYTES`` (``_one_call``) and a row a call
  where not; where any row continues (a prefix hit, a chunk) all
  ``max_seq_len`` slots are, one row at a time (a row's expanded keys and
  values at 4096 positions and 128 heads are 0.33 GB). A decode step attends
  in the ABSORBED form: ``q_nope`` is taken through ``W_uk`` into the latent space,
  scores and values are einsums over the gathered latent pages (of the live
  rows, as far as the longest of them reaches: ``models/llama.py::KVWalk``), and
  ``W_uv`` brings the result back; keys and values of cached tokens are never
  expanded. The two are the same mathematics (``tests/test_deepseek_v2.py``
  holds them together).
* **YaRN** rotary over the rope dims: ``models/llama.py::YarnScaling``.
* **Layers.** The first ``first_k_dense`` layers are
  :class:`DeepseekV2DenseLayer` (a SwiGLU MLP of ``intermediate_size``), a
  scan of their own before the main one (``LlamaModel.setup``); the rest are
  :class:`DeepseekV2MoELayer`: ``moe/layer.py::MoE`` with the group-limited
  router, its route scale, and a shared SwiGLU MLP of ``n_shared_experts x
  moe_intermediate_size`` added once.
* **The share of the experts held.** ``num_experts`` experts, from
  ``experts_held_first`` on, of the ``router_experts`` the router chooses
  among (``moe/layer.py``): expert parallelism as ONE of its chips sees it,
  without the exchange. ``router_experts=None`` holds them all.

Not here: ``tp > 1`` serving (the latent leaf has one head and is replicated,
``inference/partition.py``; the projections are not partitioned for it), int8
latent pages (refused), LoRA on the latent projections.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Mapping
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import (
    KVLayerView,
    KVWalk,
    LlamaForCausalLM,
    LlamaMLP,
    YarnScaling,
    apply_rotary,
    kv_walk,
    rotary_embedding,
)
from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralDecoderLayer
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.ops.attention import attention
from neuronx_distributed_tpu.parallel.layers import RMSNorm, RowParallelLinear
from neuronx_distributed_tpu.parallel.mesh import TP_AXIS

LATENT_LEAF = "cached_key"      # the one cache leaf: [c_kv | k_rope] a token
# what ONE call of a fresh prompt's attention may hold (``_one_call``): what
# one row of 2048 tokens at 64 heads does, so that prompts that long go a row
# a call as they always did, and 8 x 512 tokens at 32 heads go whole
PROMPT_CALL_BYTES = 256 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config(MixtralConfig):
    # ``intermediate_size`` is the DENSE layers' MLP width, as published;
    # ``num_experts`` the routed experts HELD here, ``top_k`` of
    # ``router_experts`` chosen a token
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense: int = 1
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 2
    router_experts: Optional[int] = None
    experts_held_first: int = 0
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    num_experts: int = 160
    top_k: int = 6
    rms_norm_eps: float = 1e-6
    # the published ``rope_scaling`` dict, or a YarnScaling
    rope_scaling: Any = None
    # factors on the two low-rank paths after their RMSNorms (LongCat-Flash's
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``; models/longcat_flash.py);
    # 1.0: none, and nothing is traced for it
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0
    # the router beyond V2's (moe/routing.py): how an expert is scored, a bias
    # on the scores for the choice, and what a group is scored by (V3's
    # ``noaux_tc``: "sigmoid", True, "top2_sum"; models/deepseek_v32.py)
    scoring_func: str = "softmax"
    router_selection_bias: bool = False
    group_score: str = "max"

    def __post_init__(self):
        scaling = self.rope_scaling
        if isinstance(scaling, Mapping):
            kind = scaling.get("type", scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError(f"rope_scaling of type {kind!r}: this model takes 'yarn'")
            fields = {f.name for f in dataclasses.fields(YarnScaling)}
            object.__setattr__(self, "rope_scaling", YarnScaling(
                **{k: v for k, v in scaling.items() if k in fields}))
        if self.page_dtype == "int8":
            raise ValueError(
                "page_dtype='int8' is not supported for a latent (MLA) page: "
                "a page's absmax would be taken over the latent and the rotary "
                "key together, and no such pages are served")
        routed = self.router_experts or self.num_experts
        if self.experts_held_first + self.num_experts > routed:
            raise ValueError(
                f"experts {self.experts_held_first}..+{self.num_experts} held of "
                f"{routed} routed")

    @property
    def head_dim_(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_dims(self) -> int:
        return self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        scale = self.head_dim_ ** -0.5
        s = self.rope_scaling
        if s is not None and s.mscale_all_dim:
            scale *= YarnScaling.get_mscale(s.factor, s.mscale_all_dim) ** 2
        return scale

    @property
    def kv_walk_loops(self) -> bool:
        """A one-token step reads the latent cache by the switch whatever the
        table's length (``models/llama.py::kv_walk``): the loop would carry
        128 heads x 576 float32 a row through every turn, and read 30.7 ms a
        step on the v5e where the switch reads 7.5 and the whole read 8.2
        (PERF.md, PR 38)."""
        return False

    def kv_leaf_shapes(self, batch: int) -> dict:
        """ONE leaf, no value leaf (``models/llama.py::kv_leaf_shapes``)."""
        if self.page_size:
            shape = (self.page_pool_pages, self.page_size, 1, self.latent_dim)
            return {LATENT_LEAF: (shape, jnp.dtype(self.page_dtype or self.dtype))}
        return {LATENT_LEAF: ((batch, self.max_seq_len, 1, self.latent_dim), self.dtype)}


def deepseek_v2(**over) -> DeepseekV2Config:
    """deepseek-ai/DeepSeek-V2: 236 B parameters, 21 B active."""
    return DeepseekV2Config(**{**dict(
        vocab_size=102400, hidden_size=5120, intermediate_size=12288,
        num_layers=60, num_heads=128, num_kv_heads=128, rope_theta=10000.0,
        max_seq_len=4096, rope_scaling=YarnScaling(
            factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    ), **over})


_EXACT = dict(preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _prompt_rows(cls, cfg, continues, w_uk, w_uv, q, slab, slots):
    """Some rows of a prompt over their slabs: ``q`` (rows, s_new, n, nope +
    rope) at ``slots`` (rows, s_new), ``slab`` (rows, S, rank + rope); returns
    (rows, s_new, n, v). How far a row reads is the CALL's (``continues``, a
    traced bool): where every row of the call starts at 0 (an insert without a
    prefix hit) a query can see the call's own tokens alone, slots
    0..s_new-1, and the rows are the kernel's batch; where any continues (a
    prefix hit, a chunk) each reads its S slots, one row a call. A function of
    the attention's class (``cls``: its ``_kv_up`` and ``_causal`` are what
    runs), the config and its arguments, jitted: the layer scans of a program,
    and the programs of a bucket, trace and lower one body for a shape."""
    expanded = functools.partial(cls(cfg)._expanded, w_uk=w_uk, w_uv=w_uv)
    s_new = q.shape[1]

    def continued():
        if q.shape[0] == 1:
            return expanded(q[0], slab[0], slots[0])[None]
        return jax.lax.map(lambda row: expanded(*row), (q, slab, slots))

    return jax.lax.cond(continues, continued, lambda: expanded(q, slab[:, :s_new], slots))


class DeepseekV2Attention(nn.Module):
    config: DeepseekV2Config

    @nn.compact
    def __call__(self, x: jax.Array, rope,
                 kv: Optional[KVLayerView] = None, live=None) -> jax.Array:
        cfg = self.config
        n, nope, rd, vd = (cfg.num_heads, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank = cfg.kv_lora_rank

        def kernel(name, shape, axes, scale=1.0):
            """A projection ``(fan_in, *out)``, normal with variance 1 / fan_in;
            one that follows a low-rank path scaled by ``scale`` takes
            ``1 / (scale^2 fan_in)``: the scales stand for a fan-in of the
            hidden size, and a seeded model's queries, keys and values keep
            the variance they have without them (at ``1 / fan_in`` the scores'
            spread grows by both scales' product, the softmax picks one key,
            and bf16 rounding grows with every layer: 0.42 of the largest
            logit at LongCat-Flash's widths on the v5e, PERF.md, PR 52)."""
            init = nn.initializers.variance_scaling(
                1.0 / scale ** 2, "fan_in", "normal", in_axis=0,
                out_axis=tuple(range(1, len(shape))))
            return self.param(name, nn.with_partitioning(init, axes), shape,
                              cfg.param_dtype).astype(cfg.dtype)

        def norm(name):
            return RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name)

        h = cfg.hidden_size
        w_dq = kernel("q_a_proj", (h, cfg.q_lora_rank), (None, None))
        w_uq = kernel("q_b_proj", (cfg.q_lora_rank, n, nope + rd), (None, TP_AXIS, None),
                      cfg.q_lora_scale)
        w_dkv = kernel("kv_a_proj", (h, rank + rd), (None, None))
        # the published ``kv_b_proj`` (rank -> heads x [k_nope | v]) is kept as
        # its two halves: the absorbed decode multiplies by each alone, and a
        # slice of one stacked matrix would be copied out every layer-step
        w_uk = kernel("k_b_proj", (rank, n, nope), (None, TP_AXIS, None), cfg.kv_lora_scale)
        w_uv = kernel("v_b_proj", (rank, n, vd), (None, TP_AXIS, None), cfg.kv_lora_scale)
        x = x.astype(cfg.dtype)

        def scaled(c, factor):
            if factor == 1.0:
                return c
            with jax.named_scope("mla_lora_scale"):
                return c * jnp.asarray(factor, c.dtype)

        with jax.named_scope("mla_q"):
            c_q = scaled(norm("q_a_norm")(x @ w_dq), cfg.q_lora_scale)
            q = jnp.einsum("bsr,rnd->bsnd", c_q, w_uq)
        with jax.named_scope("mla_kv_down"):
            down = x @ w_dkv
            c_kv = scaled(norm("kv_a_norm")(down[..., :rank]), cfg.kv_lora_scale)  # (b, s, rank)
            k_r = down[..., None, rank:]                               # (b, s, 1, rope)
        index = self._index(x, c_q, w_uq, kernel)
        if cfg.decode:
            o = self._cached(q, c_kv, k_r, w_uk, w_uv, kv, live, index)
        else:
            cos, sin = rope
            q = jnp.concatenate(
                [q[..., :nope], apply_rotary(q[..., nope:], cos, sin)], axis=-1)
            latent = jnp.concatenate([c_kv[:, :, None], apply_rotary(k_r, cos, sin)], axis=-1)
            s = x.shape[1]
            pos = jnp.arange(s, dtype=jnp.int32)
            if index is None:
                o = jnp.stack([self._expanded(q[i], latent[i, :, 0], pos, w_uk, w_uv)
                               for i in range(x.shape[0])])
            else:
                o = self._chosen_prompt(
                    q, latent[:, :, 0], jnp.broadcast_to(pos, x.shape[:2]),
                    self._index_rotated(index, cos, sin), None, None, w_uk, w_uv)
        return RowParallelLinear(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o_proj",
        )(o.reshape(*x.shape[:2], n * vd))

    # --- the seam of a model that scores the cached tokens and reads a chosen
    # set of them (models/deepseek_v32.py fills it). Here nothing is scored:
    # ``_index`` says None, every visible token is read and none of the
    # ``index is not None`` branches below is traced.
    def _index(self, x, c_q, w_uq, kernel):
        """What this layer's indexer makes of the tokens ``x`` and their
        normed query latents ``c_q`` (``kernel`` draws its projections), or
        None without one."""
        return None

    def _expanded(self, q, latent, positions, w_uk, w_uv):
        """ONE row in the expanded form: ``q`` (s, n, nope + rope) at
        ``positions`` (s,), ``latent`` (S, rank + rope) of cache slots 0..S-1;
        slot j is visible to a query at position p iff j <= p. Keys and values
        come up through ``w_uk`` and ``w_uv``; returns (s, n, v). With a
        leading axis of rows on all three, the rows go through ONE call."""
        return self._causal(q, *self._kv_up(latent, w_uk, w_uv), positions)

    def _one_call(self, b: int, s: int) -> bool:
        """Whether a fresh prompt of ``b`` rows x ``s`` tokens attends in ONE
        call: its queries, keys, values and outputs as the kernel takes them
        (every head of ``head_dim_``) stay under ``PROMPT_CALL_BYTES``. Else a
        row a call, as a prompt always went."""
        cfg = self.config
        a_row = 4 * s * cfg.num_heads * cfg.head_dim_ * jnp.dtype(cfg.dtype).itemsize
        return b == 1 or b * a_row <= PROMPT_CALL_BYTES

    def _kv_up(self, latent, w_uk, w_uv, heads_first=False):
        """Keys (S, n, nope + rope) and values (S, n, v) of ONE row's latents,
        for the ``n`` heads ``w_uk`` and ``w_uv`` hold; ``heads_first``: (n, S,
        ..), the way the kernel reads them, where the caller slices them by
        slots besides (models/deepseek_v32.py: a transposed copy of both is
        0.77 GB at 8192 slots and 128 heads)."""
        cfg = self.config
        n, rank, rd = w_uk.shape[1], cfg.kv_lora_rank, cfg.qk_rope_head_dim
        rows, S = latent.shape[:-2], latent.shape[-2]    # rows: none, or a fresh prompt's
        with jax.named_scope("mla_kv_up"):
            if heads_first:
                k = jnp.concatenate(
                    [jnp.einsum("jr,rnd->njd", latent[:, :rank], w_uk),
                     jnp.broadcast_to(latent[None, :, rank:], (n, S, rd))], axis=-1)
                return k, jnp.einsum("jr,rnd->njd", latent[:, :rank], w_uv)
            k = jnp.concatenate(
                [jnp.einsum("...jr,rnd->...jnd", latent[..., :rank], w_uk),
                 jnp.broadcast_to(latent[..., None, rank:], (*rows, S, n, rd))], axis=-1)
            v = jnp.einsum("...jr,rnd->...jnd", latent[..., :rank], w_uv)
        return k, v

    def _causal(self, q, k, v, positions, heads_first=False):
        """Queries ``q`` (s, n, d) at ``positions`` over every key at or
        before them: the flash kernel where it takes the shapes. ``k`` and
        ``v`` as ``_kv_up`` gives them; with a leading axis of rows on all
        four, the rows are the kernel's batch."""
        cfg = self.config
        nope, vd, rd = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.qk_rope_head_dim
        from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

        s, S = q.shape[-3], k.shape[-2 if heads_first else -3]
        blk_q, blk_k = cfg.blocks_for(s, S)
        blk_q = min(blk_q, s)
        flash = cfg.use_flash_attention and s >= 128 and flash_supported(s, S, blk_q, blk_k)
        if flash:   # the kernel takes ONE head size: v is padded to q's and k's
            v = jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, nope + rd - vd),))
        heads = (lambda a: a) if heads_first else (lambda a: a.swapaxes(-3, -2))
        batch = (lambda a: a) if q.ndim == 4 else (lambda a: a[None])
        with jax.named_scope("attend"):
            o = attention(
                batch(q.swapaxes(-3, -2)), batch(heads(k)), batch(heads(v)), causal=False,
                sm_scale=cfg.softmax_scale,
                use_flash=flash, block_q=blk_q, block_k=blk_k, q_positions=batch(positions))
        return (o if q.ndim == 4 else o[0]).swapaxes(-3, -2)[..., :vd]

    def _cached(self, q, c_kv, k_r, w_uk, w_uv, kv, live=None, index=None):
        """The serving path: the new tokens' ``[c_kv | k_rope]`` go into the
        latent leaf at their slots (through the block table where paged), and
        the queries attend over what the leaf then holds."""
        cfg = self.config
        if kv is None:
            raise ValueError(
                "decode-mode attention reads and writes the latent leaf that "
                "LlamaModel declares and its layer scan carries; apply it "
                "through LlamaModel (or pass a KVLayerView)")
        b, s_new = q.shape[:2]
        nope, dim = cfg.qk_nope_head_dim, cfg.latent_dim
        ps, S = cfg.page_size, cfg.max_seq_len
        idx_var = self.variable("cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))
        idx = idx_var.value
        slots = idx[:, None] + jnp.arange(s_new, dtype=jnp.int32)[None, :]
        cos, sin = rotary_embedding(slots, cfg.qk_rope_head_dim, cfg.rope_theta,
                                    dtype=q.dtype, scaling=cfg.rope_scaling)
        q_rope = apply_rotary(q[..., nope:], cos, sin)
        latent = jnp.concatenate([c_kv[:, :, None], apply_rotary(k_r, cos, sin)], axis=-1)
        if index is not None:
            index = self._index_rotated(index, cos, sin)
        if ps:
            bt = self.variable("cache", "block_table",
                               lambda: jnp.zeros((b, S // ps), jnp.int32))
            table = bt.value + kv.first_row(cfg.page_pool_pages)       # (b, S / ps)
        with jax.named_scope("kv_write"):
            # slots at or past max_seq_len are dropped (models/llama.py)
            pool = kv.flat(LATENT_LEAF)
            if ps:
                npages = pool.shape[0]
                phys = jnp.take_along_axis(table, jnp.clip(slots // ps, 0, S // ps - 1), axis=1)
                flat = jnp.where(slots < S, phys * ps + slots % ps, npages * ps)
                kv.put(LATENT_LEAF, pool.reshape(npages * ps, 1, dim).at[flat].set(
                    latent.astype(pool.dtype), mode="drop"))
                at = lambda leaf: leaf.reshape(npages * ps, 1, leaf.shape[-1]).at[flat]  # noqa: E731
            else:
                rows = kv.first_row(b) + jnp.arange(b)[:, None]
                kv.put(LATENT_LEAF, pool.at[rows, slots].set(
                    latent.astype(pool.dtype), mode="drop"))
                at = lambda leaf: leaf.at[rows, slots]  # noqa: E731
            if index is not None:       # the second leaf, at the same slots
                self._index_write(kv, index, at)
            idx_var.value = idx + s_new
        if s_new > 1:
            with jax.named_scope("kv_gather"):
                pool = kv.flat(LATENT_LEAF)
                if ps:
                    slab = pool[table].reshape(b, S, dim)       # whole pages, by the table
                else:
                    slab = jax.lax.dynamic_slice_in_dim(pool, kv.first_row(b), b).reshape(b, S, dim)
            qx = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            if index is not None:
                return self._chosen_prompt(qx, slab, slots, index, live,
                                           (kv, table if ps else None), w_uk, w_uv)
            attend = functools.partial(
                _prompt_rows, type(self), cfg, jnp.any(idx > 0), w_uk, w_uv)
            if self._one_call(b, s_new):
                return attend(qx, slab, slots)
            # a row a call, as a prompt always went; the call's choice is each row's
            out = jax.lax.map(lambda row: attend(*row), tuple(
                a.reshape(b, 1, *a.shape[1:]) for a in (qx, slab, slots)))
            return out.reshape(b, *out.shape[2:])
        # one new token a row: absorbed. q_nope goes INTO the latent space
        # (W_uk), the scores and the values are taken over the latent slab,
        # and W_uv brings the result out: no cached key or value is expanded.
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bsnd,rnd->bsnr", q[..., :nope], w_uk, **_EXACT)
            q_all = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)

        walk = kv_walk(cfg, idx, None if live is None else live[:, 0])
        # without an indexer the call is what it always was (whoever stands in
        # for ``_walk_attention`` takes the four arguments it always took)
        asks = {} if index is None else {"index": index}
        o_lat = self._walk_attention(q_all, kv, walk, table if ps else None, **asks)
        with jax.named_scope("mla_absorb"):
            return jnp.einsum("bsnr,rnd->bsnd", o_lat, w_uv, **_EXACT).astype(q.dtype)

    def _walk_attention(self, q_all, kv, walk: KVWalk, table, index=None):
        """``q_all`` (b, 1, n, latent_dim) float32, one new token a row in the
        latent space, over what ``walk`` reads of the latent cache (the
        prefix below its bound, of the rows in its rung); returns the
        weighted latents ``(b, 1, n, kv_lora_rank)``. With an ``index`` (what
        the rows' indexer asks, riding with the queries through the rung's
        picks) a row reads, of what is visible, the tokens ``_chosen`` says."""
        cfg = self.config
        pool = kv.flat(LATENT_LEAF)

        def attend(top, count):
            q, asks = (top.q, None) if index is None else top.q
            with jax.named_scope("kv_gather"):
                slab = top.span(pool, 0, count).reshape(-1, count * walk.chunk, cfg.latent_dim)
            chosen = None if asks is None else self._chosen(asks, kv, top, count)
            with jax.named_scope("attend"):
                scores = jnp.einsum("bsnc,bjc->bnsj", q, slab, **_EXACT) * cfg.softmax_scale
                probs = jax.nn.softmax(jnp.where(
                    (top.visible(0, count) if chosen is None else chosen)[:, None, None],
                    scores, -1e30), axis=-1)
                return jnp.einsum("bnsj,bjc->bsnc", probs, slab, **_EXACT)[..., :cfg.kv_lora_rank]

        rows = walk.rows(q_all if index is None else (q_all, index), table,
                         kv.first_row(q_all.shape[0]))
        return attend(rows, 1) if walk.n_chunks == 1 else walk.prefix(rows, attend)


class DeepseekV2DenseLayer(nn.Module):
    """A leading layer: latent attention and a SwiGLU MLP of
    ``intermediate_size``."""

    config: DeepseekV2Config
    attention_cls = DeepseekV2Attention

    @nn.compact
    def __call__(self, x: jax.Array, rope, kv=None, live=None) -> jax.Array:
        cfg = self.config
        h = cfg.make_norm(name="input_norm")(x)
        x = x + self.attention_cls(cfg, name="attention")(h, rope, kv, live)
        h = cfg.make_norm(name="post_attn_norm")(x)
        return x + LlamaMLP(cfg, name="mlp")(h)


class DeepseekV2MoELayer(nn.Module):
    """An expert layer: latent attention, then the routed experts held here
    plus the shared experts (one SwiGLU MLP, added once)."""

    config: DeepseekV2Config
    attention_cls = DeepseekV2Attention

    @nn.compact
    def __call__(self, x: jax.Array, rope, kv=None, live=None,
                 stack=None) -> jax.Array:
        cfg = self.config
        h = cfg.make_norm(name="input_norm")(x)
        x = x + self.attention_cls(cfg, name="attention")(h, rope, kv=kv, live=live)
        h = cfg.make_norm(name="post_attn_norm")(x)
        moe_out = MoE(
            num_experts=cfg.num_experts,
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
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
            router_experts=cfg.router_experts,
            experts_held_first=cfg.experts_held_first,
            n_group=cfg.n_group,
            topk_group=cfg.topk_group,
            route_scale=cfg.routed_scaling_factor,
            scoring_func=cfg.scoring_func,
            selection_bias=cfg.router_selection_bias,
            group_score=cfg.group_score,
            name="moe",
        )(h, live, None if stack is None else (kv.layer - cfg.first_k_dense, stack))
        x = x + moe_out
        if cfg.n_shared_experts:
            with jax.named_scope("shared_expert"):
                shared = dataclasses.replace(
                    cfg, intermediate_size=cfg.n_shared_experts * cfg.moe_intermediate_size)
                x = x + LlamaMLP(shared, name="shared_expert")(h)
        return x

    layer_stack = staticmethod(MixtralDecoderLayer.layer_stack)


class DeepseekV2ForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM`` (embedding, the two layer scans, final norm,
    vocab-parallel head) over DeepSeek-V2's two kinds of layer."""

    layer_cls: Any = DeepseekV2MoELayer
    dense_layer_cls: Any = DeepseekV2DenseLayer
