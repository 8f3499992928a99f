"""Expert MLP execution (reference ``modules/moe/expert_mlps.py`` —
``forward_all_experts``:139, ``forward_capacity_factor``:169, mode dispatch
``forward``:297 — and ``modules/moe/experts.py`` fused gate/up/down +
``moe_parallel_layers.py`` 3D-weight einsum linears).

TPU-native re-design (GShard/Switch dispatch algebra under GSPMD):

* Expert weights are 3D ``(E, H, I)`` with spec ``(ep, None, tp)`` — E over
  the expert-parallel mesh axis, I over TP. The reference's
  ``ExpertFusedColumnParallelLinear`` machinery becomes these annotations.
* **capacity_factor mode**: token positions inside each expert come from an
  int32 cumsum over the top-k mask — EXACT integer arithmetic, replacing the
  reference's fp64 matmul-tril cumsum (``utils/tensor_utils.py:4``,
  fp64 absent on TPU — SURVEY §7.3 hard part 4). Dispatch/combine are
  one-hot einsums; XLA lowers the token->expert resharding to the EP
  all-to-all the reference issues by hand (``mappings.py:311-338``).
* **all_experts mode**: every expert computes every token, outputs weighted
  by the combine matrix — no dropping, O(E) FLOPs, for small E or goldens.
* **grouped mode** (serving, prefill and decode alike): the dropless form.
  The ``top_k`` (token, expert) assignments are sorted by expert, the tokens'
  activations gathered in that order, and gate/up/down run as ONE grouped
  matmul each (``kernels/grouped_matmul.py``) that reads an expert's weights
  only where some row chose it: a decode step moves the touched experts'
  bytes, a prefill does ``top_k / E`` of the all-experts FLOPs. Tokens that
  are not real (dead decode rows, bucket padding) choose nothing. The
  reference's ``forward_selective_loading`` (expert_mlps.py:267, a per-token
  copy of whole expert matrices) and its ``T*top_k/E`` threshold are what
  this replaces.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels.grouped_matmul import (
    group_visits,
    grouped_matmul,
    row_tile,
    rows_multiplied,
)
from neuronx_distributed_tpu.kernels import mode as kernel_mode
from neuronx_distributed_tpu.parallel.layers import default_kernel_init
from neuronx_distributed_tpu.parallel.mesh import EP_AXIS, TP_AXIS
from neuronx_distributed_tpu.parallel.partitioning import constrain


def _silu_gated(gate: jax.Array, up: jax.Array) -> jax.Array:
    return nn.silu(gate) * up


def sort_by_expert(combine: jax.Array, top_k: int,
                   live: Optional[jax.Array] = None, share: bool = False):
    """The ``top_k`` (token, expert) assignments of ``combine (T, E)`` sorted
    by expert (a stable sort: by token within an expert), those of tokens
    that are not ``live`` (T,) last and in no group. Returns ``weight (T, k)``,
    ``order (M,)`` (row ``r`` of the sorted list is assignment ``order[r]``,
    token ``order[r] // top_k``), ``place (M,)`` (its inverse) and
    ``group_sizes (E,)``; ``M = T * top_k``.

    A counting sort: an assignment's place is its expert's first row plus the
    number of earlier tokens that chose that expert. ``lax.sort`` gives the
    same permutation and the TPU compiler needs 14 s for it at an OLMoE
    8 x 512 insert's 32 768 keys, in each of a cell's 16 insert programs.

    ``share``: ``combine`` is the held share of a wider router's choices
    (``moe/layer.py``), so a token has up to ``top_k`` nonzero weights here
    and often none. A choice of weight zero fell on an absent expert: it
    joins no group, exactly as a dead token's."""
    T, E = combine.shape
    k, C = top_k, E + 1                # column E: no expert, a dead token's
    weight, expert = lax.top_k(combine, k)                         # (T, k)
    if live is not None:
        expert = lax.select(lax.broadcast_in_dim(live, (T, k), (0,)), expert,
                            lax.full_like(expert, E))
    if share:
        expert = lax.select(lax.gt(weight, lax.full_like(weight, 0)), expert,
                            lax.full_like(expert, E))
    # (lax, not jnp, from here on: these few dozen equations are traced and
    # lowered anew for every serving program, and a jnp call costs three)
    zeros = lax.full((T, k, C), 0, jnp.int32)
    chose = lax.eq(lax.broadcast_in_dim(expert, (T, k, C), (0, 1)),
                   lax.broadcast_in_dim(np.arange(C, dtype=np.int32), (T, k, C), (2,)))
    counts = lax.reduce_sum(lax.select(chose, lax.full_like(zeros, 1), zeros), (1,))
    upto = lax.cumsum(counts, axis=0)                              # (T, C), with this token
    sizes = lax.index_in_dim(upto, T - 1, 0, keepdims=False)
    first_row = lax.sub(lax.cumsum(sizes, axis=0), sizes)
    before = lax.add(lax.sub(upto, counts), lax.broadcast_in_dim(first_row, (T, C), (1,)))
    place = lax.reduce_sum(
        lax.select(chose, lax.broadcast_in_dim(before, (T, k, C), (0, 2)), zeros), (2,))
    # a token's choices are distinct experts; those of a dead token are all
    # column E, and keep their order within the token
    dead = lax.eq(expert, np.int32(E))
    if share:      # some of a token's choices: their rank among its dead ones
        rank = lax.sub(lax.cumsum(lax.convert_element_type(dead, jnp.int32), axis=1),
                       lax.full((T, k), 1, jnp.int32))
    else:
        rank = lax.broadcast_in_dim(np.arange(k, dtype=np.int32), (T, k), (1,))
    place = lax.add(place, lax.select(
        dead, rank, lax.full((T, k), 0, jnp.int32))).reshape(T * k)
    order = jnp.zeros((T * k,), jnp.int32).at[place].set(
        np.arange(T * k, dtype=np.int32),
        mode="promise_in_bounds", unique_indices=True)
    return weight, order, place, lax.slice_in_dim(sizes, 0, E)


# tokens one grouped call takes. The call sorts ``tokens x top_k`` rows and
# gathers, multiplies and scatters all of them, real or not: at top-10 of a
# held share (``models/laguna.py``: an eighth of the rows real) a 5 x 4096
# insert's class of 32 768 tokens held 5.5 GiB of temporaries beside 7.8 GiB
# of weights and cache, and on the v5e that program never returned (PERF.md,
# PR 49). Longer calls go by slices of this many tokens, each its own class;
# no cell before PR 49 hands over more (DeepSeek-V2's 8 x 2048 is 16 384).
GROUPED_TOKENS = 16384


def token_class(tokens: int) -> int:
    """The token count a grouped call is padded to: the next power of two, 8
    at least. What is traced for one class serves every program of it."""
    return max(8, 1 << (tokens - 1).bit_length())


def grouped_rows_multiplied(group_sizes: jax.Array, tokens: int, top_k: int
                            ) -> jax.Array:
    """Rows the grouped kernel's dots run over in calls of ``tokens`` tokens
    whose group sizes are ``group_sizes (calls, E)``, as
    :func:`_grouped_experts` tiles them: a count for the serving counters, by
    the kernel's own arithmetic (``kernels/grouped_matmul.py::rows_multiplied``).
    A call of more than ``GROUPED_TOKENS`` goes by slices and is counted here
    as ONE sort at a slice's tile: each slice's group edges add at most a
    sub-tile a group, which this leaves out."""
    tm, _ = row_tile(token_class(min(tokens, GROUPED_TOKENS)) * top_k, group_sizes.shape[-1])
    return rows_multiplied(group_sizes, tm)


@functools.partial(jax.jit, static_argnames=("top_k", "glu", "dtype", "interpret",
                                              "share"))
def _grouped_experts(x, combine, live, layer, gate, up, down, *, top_k, glu,
                     dtype, interpret, share=False):
    """``ExpertMLPs.forward_grouped`` on a whole token class: ``x (T, H)``,
    ``combine (T, E)``, ``live (T,)``, the weight stacks ``(L, E, ...)`` and
    this layer's index. A function of the module, jitted, so that its trace
    (some two hundred equations with the kernels' bodies, a second of Python
    on a serving host) is kept by shape and dtype: a serving cell's 18
    programs (one per insert shape) hold seven token classes between them."""
    T, H = x.shape
    weight, order, place, group_sizes = sort_by_expert(combine, top_k, live, share)
    tm, rows = row_tile(T * top_k, combine.shape[1])
    visits = group_visits(group_sizes, rows, tm)
    xs = x.astype(dtype).at[jax.lax.div(order, np.int32(top_k))].get(  # (M, H)
        mode="promise_in_bounds")
    xs = jnp.pad(xs, ((0, rows - T * top_k), (0, 0)))      # to whole m tiles
    # gate and up share the rows and meet in the kernel's last step, on its
    # float32 sums: neither (M, I) product is written out
    a = grouped_matmul(xs, (gate, up) if glu else (gate,), layer, visits, tm,
                       _silu_gated if glu else nn.gelu, interpret=interpret)
    out = grouped_matmul(a, (down,), layer, visits, tm, interpret=interpret)
    # back to (token, choice) order; the rows of no group hold whatever the
    # kernel's buffer held, so they are selected away, not scaled
    out = out.at[place].get(mode="promise_in_bounds", unique_indices=True
                            ).reshape(T, top_k, H)
    # (under ``share`` a choice of an absent expert sits in no group either)
    real = (live[:, None, None] & (weight > 0)[:, :, None]) if share else live[:, None, None]
    out = jnp.where(real, out, 0)
    return jnp.einsum("tkh,tk->th", out.astype(jnp.float32),
                      weight.astype(jnp.float32))


class ExpertMLPs(nn.Module):
    """E parallel gated MLPs with fused 3D weights."""

    num_experts: int
    hidden_size: int
    intermediate_size: int
    glu: bool = True
    capacity_factor: float = 1.25
    mode: str = "capacity_factor"  # | "all_experts" | "grouped" (serving)
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        E, H, I = self.num_experts, self.hidden_size, self.intermediate_size
        init = default_kernel_init
        self.w_gate = self.param(
            "gate", nn.with_partitioning(init, (EP_AXIS, None, TP_AXIS)), (E, H, I),
            self.param_dtype)
        if self.glu:
            self.w_up = self.param(
                "up", nn.with_partitioning(init, (EP_AXIS, None, TP_AXIS)), (E, H, I),
                self.param_dtype)
        self.w_down = self.param(
            "down", nn.with_partitioning(init, (EP_AXIS, TP_AXIS, None)), (E, I, H),
            self.param_dtype)

    def _mlp(self, h: jax.Array) -> jax.Array:
        """h: (E, C, H) expert-major activations, E sharded over ep."""
        from neuronx_distributed_tpu.quantization.core import dequantize_leaf

        h = h.astype(self.dtype)
        # int8 serving: quantized leaves dequantize per-expert-tensor here
        wg = dequantize_leaf(self.w_gate, self.dtype).astype(self.dtype)
        wd = dequantize_leaf(self.w_down, self.dtype).astype(self.dtype)
        g = jnp.einsum("ech,ehi->eci", h, wg)
        g = constrain(g, P(EP_AXIS, None, TP_AXIS))
        if self.glu:
            u = jnp.einsum("ech,ehi->eci", h,
                           dequantize_leaf(self.w_up, self.dtype).astype(self.dtype))
            a = nn.silu(g) * u
        else:
            a = nn.gelu(g)
        out = jnp.einsum("eci,eih->ech", a, wd)
        return constrain(out, P(EP_AXIS, None, None))

    # --- capacity-factor (static shapes, token dropping) -----------------

    def capacity(self, num_tokens: int) -> int:
        c = int(self.capacity_factor * num_tokens / self.num_experts)
        return max(1, min(c, num_tokens))

    def forward_capacity_factor(self, x: jax.Array, combine: jax.Array) -> jax.Array:
        """x: (T, H) tokens; combine: (T, E) router weights (k nonzero/row).
        Returns (T, H). Tokens beyond an expert's capacity are DROPPED in
        priority order of token index (reference forward_capacity_factor
        semantics, expert_mlps.py:169-266)."""
        T, H = x.shape
        E = self.num_experts
        C = self.capacity(T)
        mask = (combine > 0).astype(jnp.int32)                    # (T, E)
        # EXACT int32 position-in-expert (reference needed fp64 matmul cumsum)
        pos = jnp.cumsum(mask, axis=0) * mask - mask              # 0-based, (T, E)
        keep = (pos < C) & (mask > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=x.dtype)  # (T, E, C); C==drop
        dispatch = pos_oh * keep[..., None].astype(x.dtype)       # (T, E, C)
        combine_w = dispatch * combine[..., None].astype(x.dtype)  # (T, E, C)

        expert_in = jnp.einsum("th,tec->ech", x, dispatch)
        expert_in = constrain(expert_in, P(EP_AXIS, None, None))   # EP all-to-all here
        expert_out = self._mlp(expert_in)
        out = jnp.einsum("ech,tec->th", expert_out, combine_w)
        return out.astype(x.dtype)

    # --- all-experts (dense, no dropping) --------------------------------

    def forward_all_experts(self, x: jax.Array, combine: jax.Array) -> jax.Array:
        """Every expert runs every token (reference forward_all_experts,
        expert_mlps.py:139-167)."""
        T, H = x.shape
        h = jnp.broadcast_to(x[None], (self.num_experts, T, H))
        out = self._mlp(h)                                         # (E, T, H)
        return jnp.einsum("eth,te->th", out, combine.astype(out.dtype)).astype(x.dtype)

    # --- grouped (serving: dropless, only the chosen experts) -------------

    def forward_grouped(self, x: jax.Array, combine: jax.Array, top_k: int,
                        live: Optional[jax.Array] = None,
                        stack=None, share: bool = False) -> jax.Array:
        """x: (T, H); combine: (T, E) with ``top_k`` nonzeros a row; ``live``
        (T,) bool says which tokens are real (None: all). Every real
        (token, expert) assignment is computed, none dropped, so the result
        is all_experts' up to the order of additions; a token that is not
        real comes out exactly zero and no expert is read on its behalf.

        ``stack = (layer, {"gate", "up", "down"})`` gives the weights of the
        whole layer stack, ``(L, E, ...)`` each, and this layer's index: under
        a layer scan this module's own weights are a slice of that stack,
        which a kernel could only be handed as a copy of all ``E`` experts
        (``models/mixtral.py::MixtralDecoderLayer.layer_stack``).

        ``share``: ``combine`` holds the held experts' columns of a wider
        router's choices; a row has at most ``top_k`` nonzeros and those that
        fell elsewhere are nobody's here (:func:`sort_by_expert`)."""
        T, H = x.shape
        if T > GROUPED_TOKENS:
            return jnp.concatenate([
                self.forward_grouped(x[at: at + GROUPED_TOKENS], combine[at: at + GROUPED_TOKENS],
                                     top_k, None if live is None else live[at: at + GROUPED_TOKENS],
                                     stack, share)
                for at in range(0, T, GROUPED_TOKENS)])
        if stack is None or stack[1]["gate"].dtype != self.dtype:
            # (a stack kept in another dtype would be cast whole, every layer)
            stack = (0, {"gate": self.w_gate.astype(self.dtype)[None],
                         "down": self.w_down.astype(self.dtype)[None],
                         "up": self.w_up.astype(self.dtype)[None] if self.glu else None})
        layer, w = stack
        pad = token_class(T) - T        # padding tokens are not live
        live = jnp.ones((T,), bool) if live is None else live
        out = _grouped_experts(
            jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(combine, ((0, pad), (0, 0))),
            jnp.pad(live, (0, pad)), jnp.asarray(layer, jnp.int32),
            w["gate"], w["up"], w["down"], top_k=min(top_k, self.num_experts),
            glu=self.glu, dtype=jnp.dtype(self.dtype),
            interpret=kernel_mode.interpret_kernels(), share=share)
        return out[:T].astype(x.dtype)

    def __call__(self, x: jax.Array, combine: jax.Array,
                 top_k: Optional[int] = None,
                 live: Optional[jax.Array] = None, stack=None,
                 share: bool = False) -> jax.Array:
        # int8 leaves ({"qweight", "scale"}) keep all_experts, whose einsums
        # fuse the dequantisation: no cell serves them
        if self.mode == "grouped" and not isinstance(self.w_gate, Mapping):
            if top_k is None:
                raise ValueError("grouped mode needs the router's top_k")
            return self.forward_grouped(x, combine, top_k, live, stack, share)
        if self.mode == "capacity_factor":
            return self.forward_capacity_factor(x, combine)
        if self.mode in ("all_experts", "grouped"):
            return self.forward_all_experts(x, combine)
        raise ValueError(f"unknown expert mode {self.mode!r}")
