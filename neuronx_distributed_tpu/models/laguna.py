"""Laguna (HF ``model_type: laguna``): window and full attention layers in one
stack, each head's output gated, a leading dense layer and then expert layers
with a shared expert, the router scoring by sigmoid.

With ``u = rmsnorm(x)`` and, by the layer's kind (``layer_types[l]``), its
query heads ``H_l`` (``num_attention_heads_per_layer[l]``) and its rope
(``rope_parameters[kind]``):

    q = u Wq (H_l, hd);  k = u Wk, v = u Wv (n_kv, hd)
    FULL:    the first ``partial_rotary_factor * hd`` dims of each head rotate
             by YaRN frequencies computed for that many dims, cos and sin
             times ``attention_factor``; the rest pass
    SLIDING: plain rope over the whole head
    scores q k / sqrt(hd), head h on KV head h // (H_l / n_kv); a key at
    position j is seen from position i iff j <= i and, on a SLIDING layer,
    j > i - sliding_window
    g = sigmoid(u Wg) (H_l,);  x' = x + concat_h(g_h a_h) Wo
    y = x' + FFN_l(rmsnorm(x'))
      layer 0 (``mlp_only_layers``): (silu(z W1) * (z W3)) W2
      the rest: s = score(z Wr) over ALL ``router_experts``, T its top k,
      w_e = route_scale * s_e / sum_T s;
      FFN = shared(z) + sum_{e in T, e held} w_e expert_e(z)

What is this file's and what is the stack's:

* **Two kinds of attention layer** are ONE class,
  :class:`LagunaAttention` over ``models/llama.py::LlamaAttention``, given the
  kind's view of the config (:meth:`LagunaConfig.of_kind`: heads, rope,
  window). The projections, the gate, the pages of a full layer, its walk and
  its prompts through the flash kernel are ``LlamaAttention``'s.
* **A window layer caches a ring a slot.** Whatever a row's length a window
  layer needs its last ``sliding_window`` tokens: cache leaves ``window_key``
  / ``window_value``, ONE ROW A SLOT of ``ring`` tokens
  (``LagunaConfig.slot_row_leaves``), stacked over the window layers only,
  beside K/V pages stacked over the full layers only. A row is HEAD-MAJOR,
  ``(n_kv, ring, hd)``: the one-token read contracts it with the KV head as
  a batch dimension, and a leaf that kept the head inside the ring slot had
  the WHOLE stacked leaf re-laid-out ahead of the slice that takes a layer's
  rows, in every branch of the rungs' switch (PERF.md, PR 50). For the same
  reason a step takes its rows out of the stack by slices, never by an array
  index. The token at position
  ``p`` lies at ``p % ring``; which position a ring slot holds follows from
  the row's length, and the mask goes by that position, so a slot's last
  tenant is never seen. ``CausalLM`` moves these rows where it moves block
  tables (PR 44's per-slot state) and refuses what would move a slot's cache
  by pages alone; a prompt writes its last ``ring`` tokens, attends over
  itself through ``flash_attention(window=)`` and reads no cache, so what
  CONTINUES a row (chunked prefill, a prefix hit) is refused too.
* **The stack** is layer 0 (full attention, dense MLP) as a scan of one, then
  a scan over the periods of ``layer_types`` that follow it (:class:`_Period`,
  after ``models/granite_hybrid.py``): each layer's parameters stacked over
  the periods, a counter a kind in the carry, the window layers of a period
  one ``nn.jit`` class traced once a program.
* **The experts** are ``moe/layer.py::MoE`` holding a share
  (``router_experts``, ``experts_held_first``) with ``scoring_func`` from the
  config; the shared expert is a ``LlamaMLP`` added once.

Serving only, one chip: no remat, no sequence or context parallelism, no
spec for the ring leaves at ``tp > 1``.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Mapping
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.llama import (
    KVLayerView,
    LlamaAttention,
    LlamaForCausalLM,
    LlamaMLP,
    YarnScaling,
    kv_page_leaf_shapes,
    kv_walk,
    rotary_embedding,
)
from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralDecoderLayer
from neuronx_distributed_tpu.moe.layer import MoE
from neuronx_distributed_tpu.ops.attention import attention
from neuronx_distributed_tpu.parallel.layers import ColumnParallelLinear, ParallelEmbedding

FULL, SLIDING = "full_attention", "sliding_attention"
WINDOW_KEY, WINDOW_VALUE = "window_key", "window_value"
_EXACT = dict(preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class KindRope:
    """One kind's rope: ``theta``, the share of a head that rotates, and the
    scaling of its frequencies (None: plain)."""
    theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    scaling: Optional[YarnScaling] = None

    @classmethod
    def of(cls, published) -> "KindRope":
        """From one entry of the published ``rope_parameters``."""
        if isinstance(published, cls):
            return published
        kind = published.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise ValueError(f"rope_type {kind!r}: this model takes 'default' or 'yarn'")
        fields = {f.name for f in dataclasses.fields(YarnScaling)}
        return cls(float(published.get("rope_theta", 10000.0)),
                   float(published.get("partial_rotary_factor", 1.0)),
                   YarnScaling(**{k: v for k, v in published.items() if k in fields})
                   if kind == "yarn" else None)


@dataclasses.dataclass(frozen=True)
class LagunaConfig(MixtralConfig):
    # ``num_heads`` is the published ``num_attention_heads`` (a full layer's);
    # ``intermediate_size`` the dense layers' MLP width; ``num_experts`` the
    # routed experts HELD here, ``top_k`` of ``router_experts`` chosen a token
    layer_types: Tuple[str, ...] = ()
    num_heads_per_layer: Tuple[int, ...] = ()
    # the published ``rope_parameters``: {kind: {...}}, kept as pairs
    rope_parameters: Any = None
    sliding_window: Optional[int] = 512
    attention_gate: Optional[str] = "per-head"      # published ``gating``
    mlp_only_layers: Tuple[int, ...] = (0,)
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    router_experts: Optional[int] = None
    experts_held_first: int = 0
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    num_experts: int = 256
    top_k: int = 10
    rms_norm_eps: float = 1e-6
    # set on the view of ONE kind (:meth:`of_kind`); None: the whole model's
    kind: Optional[str] = None

    def __post_init__(self):
        n = self.num_layers
        types = tuple(self.layer_types or (FULL,) * n)[:n]
        heads = tuple(self.num_heads_per_layer or (self.num_heads,) * n)[:n]
        ropes = self.rope_parameters or {}
        if isinstance(ropes, Mapping):
            ropes = tuple(sorted((k, KindRope.of(v)) for k, v in ropes.items()))
        for name, value in (("layer_types", types), ("num_heads_per_layer", heads),
                            ("rope_parameters", tuple(ropes)),
                            ("mlp_only_layers", tuple(self.mlp_only_layers))):
            object.__setattr__(self, name, value)
        if self.kind is not None:       # one kind's view of a config already checked
            return
        if len(types) != n or len(heads) != n or set(types) - {FULL, SLIDING}:
            raise ValueError(
                f"layer_types / num_heads_per_layer name {len(types)} / {len(heads)} layers "
                f"of kinds {sorted(set(types))}; this model takes {n} of "
                f"{FULL!r} / {SLIDING!r}")
        if self.mlp_only_layers != tuple(range(len(self.mlp_only_layers))):
            raise ValueError(f"mlp_only_layers {self.mlp_only_layers}: the dense layers lead")
        if self.first_k_dense != 1 or types[0] != FULL:
            raise ValueError("one leading dense layer of full attention, as published")
        for kind in set(types):
            if len({h for h, t in zip(heads, types) if t == kind}) != 1:
                raise ValueError(f"the {kind} layers differ in their query heads: {heads}")
        if SLIDING in types and not self.sliding_window:
            raise ValueError("sliding_attention layers need a sliding_window")
        if (n - 1) % self.period:
            raise ValueError(f"{n - 1} layers after the dense one are no whole periods")
        routed = self.router_experts or self.num_experts
        if self.experts_held_first + self.num_experts > routed:
            raise ValueError(
                f"experts {self.experts_held_first}..+{self.num_experts} held of "
                f"{routed} routed")
        if self.page_dtype == "int8" and SLIDING in types:
            raise ValueError(
                "page_dtype='int8' is not supported beside a ring a slot: no "
                "quantised form of window_key / window_value is served")

    # --- the stack ---------------------------------------------------------
    @property
    def first_k_dense(self) -> int:
        return len(self.mlp_only_layers)

    @property
    def period(self) -> int:
        """Length of the shortest period of the layers AFTER the dense one."""
        t = self.layer_types[self.first_k_dense:]
        return next(p for p in range(1, len(t) + 1)
                    if all(t[i] == t[i % p] for i in range(len(t)))) if t else 1

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def of_kind(self, kind: str) -> "LagunaConfig":
        """The config as a layer of ``kind`` reads it: its query heads, its
        rope, and a window or none."""
        rope = dict(self.rope_parameters).get(kind, KindRope())
        heads = next(h for h, t in zip(self.num_heads_per_layer, self.layer_types) if t == kind)
        return dataclasses.replace(
            self, kind=kind, num_heads=heads, rope_theta=rope.theta, rope_scaling=rope.scaling,
            sliding_window=self.sliding_window if kind == SLIDING else None)

    @property
    def rope_dims(self) -> int:
        rope = dict(self.rope_parameters).get(self.kind or FULL, KindRope())
        return int(self.head_dim_ * rope.partial_rotary_factor)

    # --- the cache -----------------------------------------------------------
    @property
    def ring(self) -> int:
        """Tokens a window layer keeps a slot: the window and a page more,
        in whole sublanes of eight."""
        return -(-(self.sliding_window + (self.page_size or 0)) // 8) * 8

    @property
    def slot_row_leaves(self) -> Tuple[str, ...]:
        """The leaves that hold ONE ROW A SLOT (``models/granite_hybrid.py``):
        the window layers' rings. ``CausalLM`` gathers and scatters them at an
        insert's slots and refuses what moves a slot's cache by pages."""
        return (WINDOW_KEY, WINDOW_VALUE) if SLIDING in self.layer_types else ()

    # a ring is written by a prompt from position 0 and by one-token steps:
    # nothing CONTINUES a row (``CausalLM.extend``, chunked prefill)
    slot_rows_continue = False

    def kv_leaf_shapes(self, batch: int) -> dict:
        """One layer's leaves of BOTH kinds (``models/llama.py::kv_leaf_shapes``):
        a full layer's K/V pages or slab, a window layer's ring rows."""
        leaves = kv_page_leaf_shapes(self, batch)
        if self.slot_row_leaves:
            row = ((batch, self.num_kv_heads, self.ring, self.head_dim_),
                   jnp.dtype(self.dtype))
            leaves[WINDOW_KEY] = leaves[WINDOW_VALUE] = row
        return leaves

    def window_walk_sums(self, walk) -> Tuple[jax.Array, jax.Array]:
        """What the window layers of ONE decode step read of their rings and
        what they needed (``inference/causal_lm.py::_walk_sums``): ring slots
        of the rows of ``walk``'s rung, and each live row's tokens inside the
        window, both times the window layers."""
        layers = self.layers_of(SLIDING)
        rung = jnp.asarray(walk.rungs, jnp.int32)[walk.rung(walk.live_rows)]
        needed = jnp.sum(jnp.minimum(walk.reach, self.sliding_window))
        return layers * rung * self.ring, layers * needed


def laguna_s_2_1(**over) -> LagunaConfig:
    """poolside/Laguna-S-2.1: 48 layers in the period ``f s s s``, 256 experts."""
    return LagunaConfig(**{**dict(
        vocab_size=100352, hidden_size=3072, intermediate_size=12288, num_layers=48,
        num_heads=48, num_kv_heads=8, head_dim=128, max_seq_len=8192,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING) * 12,
        num_heads_per_layer=(48, 72, 72, 72) * 12,
        rope_parameters={
            FULL: dict(rope_type="yarn", rope_theta=500000.0, factor=128.0,
                       original_max_position_embeddings=8192, beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.4852030263919618, partial_rotary_factor=0.5),
            SLIDING: dict(rope_type="default", rope_theta=10000.0, partial_rotary_factor=1.0)},
    ), **over})


# ------------------------------------------------------------------ attention

class LagunaAttention(LlamaAttention):
    """``LlamaAttention`` on one kind's view of the config. A full layer is
    all ``LlamaAttention``'s. A window layer's forward pass is too (the
    window reaches the flash call as ``cfg.sliding_window``); its CACHE is
    this class's: the ring."""

    def _rotate_at(self, q, k, slots):
        with jax.named_scope("rope_window" if self.config.sliding_window else "rope_full"):
            return super()._rotate_at(q, k, slots)

    def _decode_attention(self, x, q, k, v, kv, aidx=None, live=None, gate=None):
        if not self.config.sliding_window:
            return super()._decode_attention(x, q, k, v, kv, aidx, live, gate)
        cfg = self.config
        b, s_new, n, hd = q.shape
        ring, window = cfg.ring, cfg.sliding_window
        ci = self.variable("cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))
        idx = ci.value                                            # (b,)
        q, k = self._rotate_at(q, k, idx[:, None] + jnp.arange(s_new, dtype=jnp.int32)[None])
        first = kv.first_row(b)
        slot = jnp.arange(ring, dtype=jnp.int32)[None]            # (1, ring)

        def held(last):
            """``(rows, ring)``: the position each ring slot holds in a row
            whose last token stands at ``last`` (rows,); below 0: none yet."""
            return last[:, None] - (last[:, None] - slot) % ring

        if s_new > 1:
            # a prompt, from position 0 (nothing continues a row): its last
            # ``ring`` REAL tokens go to the ring, the bucket's padding does not
            real = (jnp.full((b,), s_new, jnp.int32) if live is None
                    else jnp.sum(live, axis=1, dtype=jnp.int32))
            qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))   # head-major, as the ring
            with jax.named_scope("ring_write"):
                # ring slot j takes the token at ``take[j]``, picked by a
                # product with a 0/1 matrix (exact: one 1 a row), not by a
                # gather. A gather's output has its index outermost: along
                # the tokens it drags the leaf it is written to into
                # token-major for the whole program, and the two forms over
                # head-major K/V that do not (a (row, head) at a time, or
                # along axis 2) crashed and hung the v5e (PERF.md, PR 50)
                take = jnp.clip(held(real - 1), 0, s_new - 1)
                pick = (take[:, :, None] == jnp.arange(s_new, dtype=jnp.int32)).astype(k.dtype)
                for name, new in ((WINDOW_KEY, kt), (WINDOW_VALUE, vt)):
                    flat = kv.flat(name)                          # (L_w * b, n_kv, ring, hd)
                    tokens = jnp.einsum("bjs,bksd->bkjd", pick, new, **_EXACT)
                    kv.put(name, jax.lax.dynamic_update_slice_in_dim(
                        flat, tokens.astype(flat.dtype), first, axis=0))
            ci.value = idx + s_new
            from neuronx_distributed_tpu.kernels.flash_attn import flash_supported

            blk = min(cfg.attention_block_q or 512, s_new)
            with jax.named_scope("attend_window"):
                o = attention(
                    qt, kt, vt, causal=True, sm_scale=cfg.attention_multiplier,
                    use_flash=(cfg.use_flash_attention and s_new >= 128
                               and flash_supported(s_new, s_new, blk, blk)),
                    block_q=blk, block_k=blk, window=window).transpose(0, 2, 1, 3)
            return self._o_proj(o.reshape(b, s_new, -1), aidx, gate)

        # one new token a row, written at ``idx % ring`` (a row that is not
        # live writes nothing: its ring is its next tenant's or nobody's),
        # then ONE read of the rings of the rung of rows that holds the live ones
        row_live = None if live is None else live[:, 0]
        n_kv = k.shape[2]
        with jax.named_scope("ring_write"):
            rows = first + jnp.arange(b)
            if row_live is not None:
                rows = jnp.where(row_live, rows, kv.leaves[WINDOW_KEY].shape[0] * b)
            # a (row, head) at a time over the leaf as (rows x n_kv, ring, hd),
            # a free reshape: a scatter wants its index outermost, and one
            # over (row, ring slot) with the head between them re-laid-out the
            # whole leaf token-major for the block and back in every branch
            at = (rows[:, None] * n_kv + jnp.arange(n_kv)).reshape(-1)
            slot_at = jnp.repeat(idx % ring, n_kv)
            for name, new in ((WINDOW_KEY, k), (WINDOW_VALUE, v)):
                flat = kv.flat(name)
                kv.put(name, flat.reshape(-1, ring, hd).at[at, slot_at].set(
                    new[:, 0].reshape(-1, hd).astype(flat.dtype), mode="drop"))
        ci.value = idx + 1
        scale = cfg.attention_multiplier or 1.0 / hd ** 0.5

        def attend(top):
            def rings(name):
                # by slices: an array index over the stacked rows would slice
                # ALL of them into fast memory first (a "mini gather")
                flat = kv.flat(name)
                if jnp.ndim(top.slab) == 0:     # the batch as it stands
                    return jax.lax.dynamic_slice_in_dim(flat, top.slab, top.idx.shape[0])
                return jnp.concatenate([jax.lax.dynamic_slice_in_dim(flat, row, 1)
                                        for row in top.slab])    # the picked rows
            at = held(top.idx)
            seen = (at >= 0) & (at > top.idx[:, None] - window)
            qg = top.q.reshape(-1, n_kv, n // n_kv, hd)
            scores = jnp.einsum("bkgd,bkjd->bkgj", qg, rings(WINDOW_KEY), **_EXACT) * scale
            probs = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
            return jnp.einsum("bkgj,bkjd->bkgd", probs, rings(WINDOW_VALUE), **_EXACT)

        walk = kv_walk(cfg, idx, row_live)
        rows = walk.rows(q, None, first)
        with jax.named_scope("attend_window"):
            if len(walk.rungs) == 1:
                o = attend(rows)
            else:
                o = jax.lax.switch(walk.rung(walk.live_rows),
                                   [functools.partial(rows.attend, r, attend)
                                    for r in walk.rungs])
        return self._o_proj(o.reshape(b, 1, -1).astype(q.dtype), aidx, gate)


# ------------------------------------------------------------------ the stack

class LagunaLayer(nn.Module):
    """A layer of either kind of attention and either kind of FFN. ``cache``:
    ``(layers of this kind so far, this kind's leaves)`` or None; ``stack``:
    ``(this layer's index in it, the expert weights of every period)`` for
    the grouped kernel (``models/mixtral.py::MixtralDecoderLayer.layer_stack``).
    Arrays in and arrays out, so that :class:`_Period` can hand the window
    layers to ``nn.jit``; returns ``(x, the leaves as the layer leaves them)``."""

    config: LagunaConfig
    kind: str
    dense: bool = False

    @nn.compact
    def __call__(self, x, cache=None, rope=None, live=None, stack=None):
        cfg = self.config
        view = None if cache is None else KVLayerView(*cache)
        h = cfg.make_norm(name="input_norm")(x)
        x = x + LagunaAttention(cfg.of_kind(self.kind), name="attention")(h, rope, view, live)
        h = cfg.make_norm(name="post_attn_norm")(x)
        if self.dense:
            x = x + LlamaMLP(cfg, name="mlp")(h)
            return x, None if view is None else view.leaves
        x = x + MoE(
            num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size, top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, router=cfg.router, mode=cfg.moe_mode,
            capacity_factor=cfg.capacity_factor, aux_loss_coef=cfg.aux_loss_coef,
            z_loss_coef=cfg.z_loss_coef, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            inference=cfg.decode, router_experts=cfg.router_experts,
            experts_held_first=cfg.experts_held_first,
            route_scale=cfg.routed_scaling_factor, scoring_func=cfg.scoring_func,
            name="moe",
        )(h, live, stack)
        with jax.named_scope("shared_expert"):
            shared = dataclasses.replace(
                cfg, intermediate_size=cfg.shared_expert_intermediate_size)
            x = x + LlamaMLP(shared, name="shared_expert")(h)
        return x, None if view is None else view.leaves


# ONE transformed class: ``nn.jit`` keeps its traces with the class it returns
_JitLayer = nn.jit(LagunaLayer)


def _layer(cfg, cls, kind, dense, name, carry, rope, live, stack=None):
    """One layer on the stack's carry ``(x, period, caches)``: its own kind's
    leaves in, the kind's counter one higher out."""
    x, period, caches = carry
    caches = dict(caches or {})
    cache = caches.get(kind)
    x, leaves = cls(cfg, kind, dense, name=name)(
        x, cache, None if rope is None else rope[kind], live,
        None if stack is None else (period, stack[name]))
    if cache is not None:
        caches[kind] = (cache[0] + 1, leaves)
    return x, period, caches or None


class _First(nn.Module):
    """Body of the scan of ONE over the leading dense layer: a scan so that
    its small cache leaves are stacked ``(1, rows)`` as every layer's are."""

    config: LagunaConfig

    @nn.compact
    def __call__(self, carry, rope=None, live=None):
        kind = self.config.layer_types[0]
        return _layer(self.config, LagunaLayer, kind, True, "block", carry, rope, live), None


class _Period(nn.Module):
    """Body of the scan over periods: the layers of one period as they stand
    (``models/granite_hybrid.py::_Period``). The window layers go through
    ``nn.jit``: the three of a period are traced once a program."""

    config: LagunaConfig

    @nn.compact
    def __call__(self, carry, rope=None, live=None, stack=None):
        cfg = self.config
        first = cfg.first_k_dense
        for at, kind in enumerate(cfg.layer_types[first: first + cfg.period]):
            carry = _layer(cfg, _JitLayer if kind == SLIDING else LagunaLayer, kind, False,
                           f"{kind}_{at}", carry, rope, live, stack)
        x, period, caches = carry
        return (x, period + 1, caches), None


class LagunaModel(nn.Module):
    """Embedding, the dense layer, the scanned periods, the final norm. In
    decode mode it declares the cache leaves of both kinds, each stacked over
    the layers of ITS kind, and hands them to the scans as their carry."""

    config: LagunaConfig

    def setup(self):
        cfg = self.config
        self.embed = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, shard_over="vocab",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)

        def scan(body, length):
            return nn.scan(
                body, variable_axes={"params": 0, "cache": 0, "losses": 0, "moe_stats": 0},
                split_rngs={"params": True}, length=length, in_axes=nn.broadcast,
                metadata_params={nn.meta.PARTITION_NAME: None})(cfg)

        self.first = scan(_First, cfg.first_k_dense)
        self.periods = scan(_Period, (cfg.num_layers - cfg.first_k_dense) // cfg.period)
        self.final_norm = cfg.make_norm()

    @nn.compact
    def __call__(self, input_ids: jax.Array, live=None) -> jax.Array:
        cfg = self.config
        b, s = input_ids.shape
        if s > cfg.max_seq_len:
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        x = self.embed(input_ids)
        rope, caches, pools, stack = None, None, {}, None
        if cfg.decode:
            rows = cfg.slot_row_leaves
            pools = {
                name: self.variable(
                    "cache", name, jnp.zeros,
                    (cfg.layers_of(SLIDING if name in rows else FULL), *shape), dtype)
                for name, (shape, dtype) in cfg.kv_leaf_shapes(b).items()}
            caches = {
                kind: (jnp.int32(0), {n: v.value for n, v in pools.items()
                                      if (n in rows) == (kind == SLIDING)})
                for kind in (FULL, SLIDING) if cfg.layers_of(kind)}
            stack = self.expert_stack()
        else:   # cos/sin once a kind, broadcast through the scans
            positions = jnp.arange(s, dtype=jnp.int32)
            rope = {}
            for kind in set(cfg.layer_types):
                view = cfg.of_kind(kind)
                rope[kind] = rotary_embedding(positions, view.rope_dims, view.rope_theta,
                                              dtype=x.dtype, scaling=view.rope_scaling)
        carry = (x, jnp.int32(0), caches)
        # a prompt's dense layer has no use for `live`; a step's attention has
        carry, _ = self.first(carry, rope, live if s == 1 else None)
        carry = (carry[0], jnp.int32(0), carry[2])
        (x, _, caches), _ = self.periods(carry, rope, live, stack)
        for name, pool in pools.items():
            pool.value = next(leaves[name] for _, leaves in caches.values() if name in leaves)
        return self.final_norm(x)

    def expert_stack(self):
        """``{layer of a period: its experts' weights over ALL periods}``, for
        a grouped kernel that indexes ``[period, expert]``; None at init."""
        params = nn.meta.unbox(self.periods.variables.get("params", {}))
        stack = {name: MixtralDecoderLayer.layer_stack(layer) for name, layer in params.items()}
        return stack if stack and all(v is not None for v in stack.values()) else None

    def attend(self, x: jax.Array) -> jax.Array:
        return self.embed.attend(x)


class LagunaForCausalLM(LlamaForCausalLM):
    """``LlamaForCausalLM``'s head and entry points over :class:`LagunaModel`."""

    def setup(self):
        cfg = self.config
        self.model = LagunaModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.vocab_size, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype)
