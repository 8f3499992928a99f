"""Granite-4.0-H (HF ``model_type: granitemoehybrid``): a stack that is a
period of two kinds of layer, Mamba-2 and attention, with a shared MLP after
each, Granite's four multipliers and no positions.

    h0 = embedding_multiplier * embed(ids)
    x  = x + residual_multiplier * mixer(rmsnorm(x))
    x  = x + residual_multiplier * mlp(rmsnorm(x))       gated SiLU, no experts
    logits = embed^T rmsnorm(x) / logits_scaling          (tied)

The attention mixer is :class:`~neuronx_distributed_tpu.models.llama.LlamaAttention`
told not to rotate and to scale its scores by ``attention_multiplier``. The
Mamba-2 mixer is :class:`Mamba2Mixer`.

What is new to the serving path, and where it lives:

* **State beside pages.** A Mamba layer keeps, for every slot of the batch,
  a state ``(heads, d_head, d_state)`` and the last ``d_conv - 1`` inputs of
  its convolution: cache leaves ``ssm_state`` and ``conv_state``, ONE ROW A
  SLOT (``GraniteHybridConfig.slot_row_leaves``), not pages of a pool. The
  config declares them beside the K/V pages (``kv_leaf_shapes``); ``CausalLM``
  moves rows of them where it moves block tables, and refuses whatever would
  move a slot's cache by pages alone.
* **A counter a kind.** The K/V leaves are stacked over the attention layers
  only and the state leaves over the Mamba layers only. The layer loop's
  carry holds, for each kind, ``(layers of that kind so far, leaves)``, and a
  layer sees a :class:`~neuronx_distributed_tpu.models.llama.KVLayerView` of
  its own kind's.
* **The stack is a scan over periods.** ``layer_types`` is cut into its
  shortest period (``m m m m m A m m m m`` four times); the scan's body holds
  the ten layers of one period as they stand, each layer's parameters
  stacked over the periods; the nine Mamba layers are one ``nn.jit`` class,
  traced once a program (:class:`_Period`). (An inner scan over a run of
  Mamba layers would compile three bodies instead of ten, but the outer
  scan then hands the inner one its slice of the stacked weights as a COPY:
  compiled for a described v5e, a decode step copied all 5.5 GB of the
  Mamba layers' weights before reading them; PERF.md, PR 44.)
* **Padding.** A prompt is padded to its bucket and a recurrence would run
  over the padding. ``live`` (b, s) says which positions are real: a padded
  position gets ``dt = 0`` (decay 1, no input), so the state after the bucket
  IS the state after the row's last real token, and the convolution's tail
  kept is the last ``d_conv - 1`` REAL inputs. In a one-token step a row that
  is not live keeps its state.
* **The step's recurrence is a kernel.** One token a row on a state goes
  through ``kernels/ssm_step.py``: the layer's rows of the stacked
  ``ssm_state`` leaf are read, stepped, reduced to ``y`` and written where
  they were, once. In XLA the same expressions were a reduction and an
  in-place update that each read the rows (PERF.md, PR 45). A prompt and a
  forward pass that keeps nothing, whatever its length, take the chunked
  form (``ssd_chunked``), which is plain XLA.

Serving only, one chip: no remat, no sequence or context parallelism, and
the state leaves have no spec for ``tp > 1`` (``inference/partition.py``
refuses).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.kernels.ssm_step import ssm_step
from neuronx_distributed_tpu.models.llama import (
    KVLayerView,
    LlamaAttention,
    LlamaConfig,
    LlamaMLP,
    kv_page_leaf_shapes,
    rotary_embedding,
)
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    default_kernel_init,
)

MAMBA, ATTENTION = "mamba", "attention"
STATE_LEAF, CONV_LEAF = "ssm_state", "conv_state"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(LlamaConfig):
    # one of "mamba" / "attention" a layer, as published
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    residual_multiplier: float = 1.0
    # "nope": nothing is rotated; "rope": the attention layers rotate q and k
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    # float32: 36 layers' rounding of a state that is read and written every
    # step would otherwise add up over an answer's length
    ssm_state_dtype: Any = jnp.float32

    def __post_init__(self):
        types = tuple(self.layer_types) or (ATTENTION,) * self.num_layers
        object.__setattr__(self, "layer_types", types)
        object.__setattr__(self, "use_rope", self.position_embedding_type == "rope")
        if len(types) != self.num_layers or set(types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types names {len(types)} layers of kinds {sorted(set(types))}; "
                f"this model takes {self.num_layers} of 'mamba' / 'attention'")
        if self.position_embedding_type not in ("nope", "rope"):
            raise ValueError(f"position_embedding_type {self.position_embedding_type!r}")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {self.mamba_n_heads * self.mamba_d_head} "
                f"is not mamba_expand x hidden_size = {self.mamba_expand * self.hidden_size}")
        if self.mamba_n_groups != 1 or self.mamba_proj_bias:
            raise ValueError("one B/C group and projections without bias only "
                             "(mamba_n_groups 1, mamba_proj_bias false)")
        if self.page_dtype == "int8" and MAMBA in types:
            raise ValueError(
                "page_dtype='int8' is not supported beside per-slot state: no "
                "quantised form of ssm_state / conv_state is served")

    # --- the stack ---------------------------------------------------------
    @property
    def period(self) -> int:
        """Length of the shortest period of ``layer_types`` (the whole stack
        where it has none)."""
        t, n = self.layer_types, self.num_layers
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and all(t[i] == t[i % p] for i in range(n)))

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    # --- the Mamba-2 mixer's widths ------------------------------------------
    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    # --- the cache -----------------------------------------------------------
    @property
    def slot_row_leaves(self) -> Tuple[str, ...]:
        """The cache leaves that hold ONE ROW A SLOT, ``(layers, rows, ...)``,
        and are neither pages nor a slab: a page-sharing prefix hit restores
        none of them. ``CausalLM`` tells a model that has any which positions
        of an insert's bucket are real and which rows of a step are live."""
        return (STATE_LEAF, CONV_LEAF) if MAMBA in self.layer_types else ()

    def kv_leaf_shapes(self, batch: int) -> dict:
        """One layer's leaves of BOTH kinds (``models/llama.py::kv_leaf_shapes``):
        an attention layer's K/V pages or slab, a Mamba layer's rows. Which
        kind a leaf is: :attr:`slot_row_leaves`."""
        leaves = kv_page_leaf_shapes(self, batch)
        if self.slot_row_leaves:
            leaves[STATE_LEAF] = ((batch, self.mamba_n_heads, self.mamba_d_head,
                                   self.mamba_d_state), jnp.dtype(self.ssm_state_dtype))
            leaves[CONV_LEAF] = ((batch, self.mamba_d_conv - 1, self.conv_dim),
                                 jnp.dtype(self.dtype))
        return leaves

    def scan_positions(self, s: int) -> int:
        """Positions the chunked scan runs over for a prompt padded to ``s``."""
        chunk = min(self.mamba_chunk_size, s)
        return -(-s // chunk) * chunk


def granite_4_0_h_micro(**over) -> GraniteHybridConfig:
    """ibm-granite/granite-4.0-h-micro: 3.19 B parameters, 36 Mamba-2 layers
    and 4 attention layers in the period ``m m m m m A m m m m``."""
    return GraniteHybridConfig(**{**dict(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192, num_layers=40,
        num_heads=32, num_kv_heads=8, head_dim=64, rms_norm_eps=1e-5,
        layer_types=((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4,
        attention_multiplier=0.015625, embedding_multiplier=12.0, logits_scaling=8.0,
        residual_multiplier=0.22, max_seq_len=4096,
    ), **over})


# ------------------------------------------------------------ the recurrence

def ssd_chunked(x, dt, dA, B, C, state, chunk: int):
    """Mamba-2's recurrence over a prompt in the chunked (SSD) form.

    ``x`` (b, s, h, p) inputs; ``dt`` (b, s, h) float32 step sizes, 0 at a
    position that is not real; ``dA = dt * A`` (b, s, h) float32, the log of
    each position's decay; ``B``, ``C`` (b, s, n), one group; ``state``
    (b, h, p, n) float32 before the first position. Returns ``(y (b, s, h, p)
    float32, state after the last position)`` of

        S_t = exp(dA_t) S_{t-1} + dt_t x_t (x) B_t,     y_t = S_t C_t

    Inside a chunk of ``chunk`` positions: ``y_t = sum_{s <= t} (C_t . B_s)
    exp(cum_t - cum_s) dt_s x_s`` plus what the carried state adds, ``exp(cum_t)
    S C_t``, with ``cum`` the running sum of ``dA`` in the chunk; between
    chunks the state is carried by a ``lax.scan``. Decays and sums in float32;
    the four products take operands in ``x``'s dtype and accumulate float32.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    dtype = x.dtype
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:     # positions that are not real: no decay, no input
        x, dt, dA, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                           for a in (x, dt, dA, B, C))
    c = (s + pad) // chunk

    def chunks(a):      # (b, c * q, ...) -> (c, b, q, ...)
        return jnp.moveaxis(a.reshape(b, c, chunk, *a.shape[2:]), 1, 0)

    f32 = dict(preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(S, inp):
        x_c, dt_c, dA_c, B_c, C_c = inp
        cum = jnp.cumsum(dA_c, axis=1)                                  # (b, q, h)
        cum_h = cum.transpose(0, 2, 1)                                  # (b, h, q)
        # what the positions of the chunk give each other
        cb = jnp.einsum("btn,bsn->bts", C_c, B_c, **f32)                # (b, q, q)
        seg = cum_h[:, :, :, None] - cum_h[:, :, None, :]               # (b, h, t, s)
        decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
        weights = cb[:, None] * decay * dt_c.transpose(0, 2, 1)[:, :, None, :]
        y = jnp.einsum("bhts,bshp->bthp", weights.astype(dtype), x_c, **f32)
        # what the carried state gives them
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", C_c, S.astype(dtype), **f32)
        # the state after the chunk
        to_end = jnp.exp(cum[:, -1:, :] - cum) * dt_c                   # (b, q, h)
        gain = jnp.einsum("bshp,bsn->bhpn", (x_c * to_end[..., None]).astype(dtype),
                          B_c, **f32)
        S = jnp.exp(cum[:, -1])[:, :, None, None] * S + gain
        return S, y

    state, y = jax.lax.scan(body, state.astype(jnp.float32),
                            tuple(chunks(a) for a in (x, dt, dA, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, h, p)
    return y[:, :s], state


def _dt_bias_init(dt_min=0.001, dt_max=0.1, floor=1e-4):
    """``dt`` log-uniform in [dt_min, dt_max], stored through the inverse of
    softplus, as the published Mamba-2 code draws it."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                                 + math.log(dt_min)), floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(lo=1.0, hi=16.0):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi)).astype(dtype)
    return init


def _conv_init(width: int):
    """A depthwise ``Conv1d``'s default: uniform in +- 1 / sqrt(fan-in), and
    the fan-in of a depthwise kernel is its width."""
    bound = 1.0 / math.sqrt(width)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """``[z, xBC, dt] = W_in u``; ``xBC`` through a causal depthwise
    convolution of width ``d_conv`` and SiLU, split into ``x`` (heads x
    d_head), ``B`` and ``C`` (d_state each); ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)`` a head; the recurrence ``S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y = rmsnorm(y * silu(z))
    * w`` over all ``d_inner``; ``out = W_out y``.

    ``state``: this layer's view of the ``ssm_state`` / ``conv_state`` leaves
    (decode mode), None for a forward pass that keeps nothing. ``live``
    (b, s) bool: the real positions of a prompt, the live rows of a step;
    None counts everything. A prompt (``s > 1``) continues from the rows'
    state as it is given (an insert hands a fresh request zeros) by the
    chunked form; one token a row is the recurrence once, on a state by
    ``kernels/ssm_step.py`` over the stacked leaf in place.

    Two departures from the published layout of the weights, for a converter
    to apply. The convolution's kernel is stored ``(d_conv, channels)``, the
    transpose of the published ``(channels, 1, d_conv)``: tap ``j`` multiplies
    the input ``d_conv - 1 - j`` positions back. The published ``in_proj``
    (hidden x [z | xBC | dt]) is kept as ``in_proj`` (hidden x [z | xBC],
    8448 wide) and ``dt_proj`` (hidden x heads): at 8512 columns, not a
    multiple of the chip's 128 lanes, the chip keeps the matrix transposed
    and every program began by copying all 36 of them (1.2 GB) into the
    order its matmul reads (compiled for a described v5e; PERF.md, PR 44)."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u: jax.Array, state: Optional[KVLayerView] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        b, s, _ = u.shape
        h, p, n, k = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv
        d_inner, conv_dim = cfg.d_inner, cfg.conv_dim
        f32 = jnp.float32

        def vector(name, init, shape):
            return self.param(name, nn.with_partitioning(init, (None,) * len(shape)),
                              shape, cfg.param_dtype)

        with jax.named_scope("ssm_in_proj"):
            def proj(name, width):
                return ColumnParallelLinear(width, use_bias=False, dtype=cfg.dtype,
                                            param_dtype=cfg.param_dtype, name=name)(u)

            z, xbc = jnp.split(proj("in_proj", d_inner + conv_dim), [d_inner], axis=-1)
            dt = proj("dt_proj", h)
        w_conv = vector("conv_kernel", _conv_init(k), (k, conv_dim)).astype(cfg.dtype)
        b_conv = (vector("conv_bias", _conv_init(k), (conv_dim,)).astype(cfg.dtype)
                  if cfg.mamba_conv_bias else None)
        dt_bias = vector("dt_bias", _dt_bias_init(), (h,)).astype(f32)
        A = -jnp.exp(vector("A_log", _a_log_init(), (h,)).astype(f32))
        D = vector("D", nn.initializers.ones_init(), (h,)).astype(f32)
        w_norm = vector("norm", nn.initializers.ones_init(), (d_inner,))

        if state is None:
            tail = jnp.zeros((b, k - 1, conv_dim), cfg.dtype)
        else:
            first = state.first_row(b)
            tail = jax.lax.dynamic_slice_in_dim(state.flat(CONV_LEAF), first, b)
        tail_in = tail

        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate([tail.astype(cfg.dtype), xbc], axis=1)   # (b, k-1+s, c)
            conv = sum(w_conv[j] * window[:, j: j + s] for j in range(k))
            if b_conv is not None:
                conv = conv + b_conv
            conv = nn.silu(conv)
            # the last k-1 REAL inputs: a row of ``real`` of them ends at
            # window position k - 2 + real
            real = (jnp.full((b,), s, jnp.int32) if live is None
                    else jnp.sum(live, axis=1, dtype=jnp.int32))
            tail = jax.vmap(lambda w, r: jax.lax.dynamic_slice_in_dim(w, r, k - 1))(
                window, real)
        x, B, C = jnp.split(conv, [d_inner, d_inner + n], axis=-1)
        x = x.reshape(b, s, h, p)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)                       # (b, s, h)
        if live is not None:
            dt = jnp.where(live[..., None], dt, 0.0)

        if s == 1 and state is not None:
            # the kernel steps this layer's rows of the whole leaf in place: no
            # slice of them before it and no update after it, each a copy
            with jax.named_scope("ssm_step"):
                dt1 = dt[:, 0]
                flat, y = ssm_step(state.flat(STATE_LEAF), first, jnp.exp(dt1 * A),
                                   dt1[..., None] * x[:, 0].astype(f32), B[:, 0], C[:, 0],
                                   None if live is None else live[:, 0])
            state.put(STATE_LEAF, flat)
            y = y[:, None]
        else:       # a prompt, or a pass that keeps nothing: the chunked form
            S = (jnp.zeros((b, h, p, n), f32) if state is None else
                 jax.lax.dynamic_slice_in_dim(state.flat(STATE_LEAF), first, b))
            with jax.named_scope("ssm_scan"):
                y, S = ssd_chunked(x, dt, dt * A, B, C, S, cfg.mamba_chunk_size)
            if state is not None:
                flat = state.flat(STATE_LEAF)
                state.put(STATE_LEAF, jax.lax.dynamic_update_slice_in_dim(
                    flat, S.astype(flat.dtype), first, axis=0))
        y = y + D[:, None] * x.astype(f32)

        if state is not None:
            if live is not None and s == 1:     # a row that is not live keeps its tail
                tail = jnp.where(live[:, :, None], tail, tail_in)
            flat = state.flat(CONV_LEAF)
            state.put(CONV_LEAF, jax.lax.dynamic_update_slice_in_dim(
                flat, tail.astype(flat.dtype), first, axis=0))

        with jax.named_scope("ssm_gate_norm"):
            y = y.reshape(b, s, d_inner) * nn.silu(z.astype(f32))
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            y = y.astype(cfg.dtype) * w_norm.astype(cfg.dtype)
        with jax.named_scope("ssm_out_proj"):
            w_out = self.param("out_proj", nn.with_partitioning(default_kernel_init, (None, None)),
                               (d_inner, cfg.hidden_size), cfg.param_dtype)
            return y @ w_out.astype(cfg.dtype)


# ------------------------------------------------------------------ the stack

class GraniteLayer(nn.Module):
    """A layer of either kind: its mixer, then the shared MLP, each after a
    norm and scaled into the residual. ``cache``: ``(layers of this kind so
    far, this kind's leaves)`` or None; returns ``(x, the leaves as the layer
    leaves them)``. Arrays in and arrays out, so that :class:`_Period` can
    hand the Mamba layers to ``nn.jit``."""

    config: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, cache=None, live=None, rope=None):
        cfg = self.config
        view = None if cache is None else KVLayerView(*cache)
        h = cfg.make_norm(name="input_norm")(x)
        mixed = (Mamba2Mixer(cfg, name="mamba")(h, view, live) if self.kind == MAMBA
                 else LlamaAttention(cfg, name="attention")(h, rope, view, live))
        x = x + cfg.residual_multiplier * mixed
        h = cfg.make_norm(name="post_mixer_norm")(x)
        x = x + cfg.residual_multiplier * LlamaMLP(cfg, name="mlp")(h)
        return x, None if view is None else view.leaves


# ONE transformed class: ``nn.jit`` keeps its traces with the class it returns
_JitLayer = nn.jit(GraniteLayer)


class _Period(nn.Module):
    """Body of the stack's scan: the layers of one period. The carry is
    ``(x, caches)``: ``caches`` None outside decode mode, else ``{kind:
    (layers of that kind so far, that kind's leaves)}``; a layer works on its
    own kind's and leaves the counter one higher.

    The Mamba layers go through ``nn.jit``, which knows a module by its
    fields and not by its name: the nine of a period are traced ONCE a
    program (and not again by the second pass ``nn.scan`` makes over its
    body), and XLA inlines the calls. The serving path builds some twenty
    programs of this body (one a row count of an insert), and tracing nine
    copies twice was most of what each cost the host (PERF.md, PR 44)."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, carry, rope=None, live=None):
        cfg = self.config
        x, caches = carry
        caches = dict(caches or {})
        for at, kind in enumerate(cfg.layer_types[: cfg.period]):
            cache = caches.get(kind)
            if kind == MAMBA:
                x, leaves = _JitLayer(cfg, kind, name=f"{kind}_{at}")(x, cache, live)
            else:
                x, leaves = GraniteLayer(cfg, kind, name=f"{kind}_{at}")(
                    x, cache, live, rope)
            if cache is not None:
                caches[kind] = (cache[0] + 1, leaves)
        return (x, caches or None), None


class GraniteHybridModel(nn.Module):
    """Embedding (times ``embedding_multiplier``), the scanned periods, the
    final norm. In decode mode it declares the cache leaves of both kinds,
    each stacked over the layers of ITS kind, and hands them to the scan as
    its carry (``models/llama.py::KVLayerView`` says why a carry)."""

    config: GraniteHybridConfig

    def setup(self):
        cfg = self.config
        self.embed = ParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, shard_over="vocab",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        self.periods = nn.scan(
            _Period, variable_axes={"params": 0, "cache": 0}, split_rngs={"params": True},
            length=cfg.num_layers // cfg.period, in_axes=nn.broadcast,
            metadata_params={nn.meta.PARTITION_NAME: None},
        )(cfg)
        self.final_norm = cfg.make_norm()

    @nn.compact
    def __call__(self, input_ids: jax.Array, live=None) -> jax.Array:
        cfg = self.config
        b, s = input_ids.shape
        if s > cfg.max_seq_len:
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        x = self.embed(input_ids)
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        rope = None
        if cfg.use_rope and not cfg.decode:
            rope = rotary_embedding(jnp.arange(s, dtype=jnp.int32), cfg.rope_dims,
                                    cfg.rope_theta, dtype=x.dtype, scaling=cfg.rope_scaling)
        caches, pools = None, {}
        if cfg.decode:
            rows = cfg.slot_row_leaves
            pools = {
                name: self.variable(
                    "cache", name, jnp.zeros,
                    (cfg.layers_of(MAMBA if name in rows else ATTENTION), *shape), dtype)
                for name, (shape, dtype) in cfg.kv_leaf_shapes(b).items()}
            caches = {
                kind: (jnp.int32(0), {n: v.value for n, v in pools.items()
                                      if (n in rows) == (kind == MAMBA)})
                for kind in (ATTENTION, MAMBA) if cfg.layers_of(kind)}
        args = (rope, live)
        while args and args[-1] is None:
            args = args[:-1]
        (x, caches), _ = self.periods((x, caches), *args)
        for name, pool in pools.items():
            pool.value = next(leaves[name] for _, leaves in caches.values() if name in leaves)
        return self.final_norm(x)

    def attend(self, x: jax.Array) -> jax.Array:
        return self.embed.attend(x)


class GraniteHybridForCausalLM(nn.Module):
    """The model and its head: the embedding's transpose where tied, divided
    by ``logits_scaling``. ``live`` (b, s) bool: the real positions of a padded
    prompt, the live rows of a one-token step (None counts everything)."""

    config: GraniteHybridConfig

    def setup(self):
        cfg = self.config
        self.model = GraniteHybridModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.vocab_size, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype)

    def _head(self, x: jax.Array) -> jax.Array:
        logits = (self.model.attend(x) if self.config.tie_word_embeddings
                  else self.lm_head(x))
        return logits / jnp.asarray(self.config.logits_scaling, logits.dtype)

    def __call__(self, input_ids: jax.Array, live=None) -> jax.Array:
        return self._head(self.model(input_ids, live=live))

    def last_logits(self, input_ids: jax.Array, last: jax.Array, live=None) -> jax.Array:
        """``(b, vocab)`` logits of ONE position a row (``LlamaForCausalLM.last_logits``)."""
        x = self.model(input_ids, live=live)
        return self._head(x[jnp.arange(x.shape[0]), last][:, None])[:, 0]
