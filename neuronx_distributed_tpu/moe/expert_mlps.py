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
    joins no group, exactly as a dead token's. The rows of the groups come
    FIRST in the list (``sum(group_sizes)`` of them), so the picks that
    fell here are a prefix of ``order``, and a caller that hands on only
    those takes slices of it (:func:`_grouped_share`)."""
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


# tokens one grouped call takes. The call sorts ``tokens x top_k`` picks, and
# where every expert is held it gathers, multiplies and scatters all of them:
# at top-10 a 5 x 4096 insert's class of 32 768 tokens held 5.5 GiB of
# temporaries beside 7.8 GiB of weights and cache, and on the v5e that program
# never returned (PERF.md, PR 49). Longer calls go by slices of this many
# tokens, each its own class; no cell before PR 49 hands over more
# (DeepSeek-V2's 8 x 2048 is 16 384). A layer that holds a share now hands on
# ``row_bound`` rows a pass, a quarter of that at an eighth held (PR 51); the
# slices stay as they are until a chip run of their own says otherwise.
GROUPED_TOKENS = 16384


def token_class(tokens: int) -> int:
    """The token count a grouped call is padded to: the next power of two, 8
    at least. What is traced for one class serves every program of it."""
    return max(8, 1 << (tokens - 1).bit_length())


def row_bound(tokens: int, top_k: int, held: int, routed: Optional[int]) -> int:
    """Rows of the sorted list ONE pass of a grouped call of ``tokens`` (a
    token class) gathers, multiplies and combines. Where every expert is held
    (``routed`` None or ``held``) every live pick is a row: all
    ``tokens x top_k``. A layer that holds ``held`` of ``routed`` experts
    expects ``held / routed`` of the picks to fall on its own: the bound is
    the largest power-of-two part of the list that holds twice that (a
    quarter at an eighth held) and no less than one 256-row tile, so every
    decode step and every short call is one pass over the whole list, the
    program it always was. What falls beyond the bound goes by further passes
    (:func:`_grouped_share`): the bound sets a pass's cost, never the
    result."""
    m = tokens * top_k
    parts = 1
    while (routed and 4 * parts * held <= routed and m % (2 * parts) == 0
           and m // (2 * parts) >= 256):
        parts *= 2
    return m // parts


def _slices(tokens: int):
    """``(first token, tokens)`` of the slices a grouped call of ``tokens``
    goes by (:data:`GROUPED_TOKENS`)."""
    return [(at, min(GROUPED_TOKENS, tokens - at)) for at in range(0, tokens, GROUPED_TOKENS)]


def grouped_rows_multiplied(group_sizes: jax.Array, tokens: int, top_k: int
                            ) -> jax.Array:
    """Rows the grouped kernel's dots run over in calls of ``tokens`` tokens
    whose group sizes are ``group_sizes (calls, E)``, as
    :func:`_grouped_experts` tiles them where every expert is held: a count
    for the serving counters, by the kernel's own arithmetic
    (``kernels/grouped_matmul.py::rows_multiplied``).
    A call of more than ``GROUPED_TOKENS`` goes by slices and is counted here
    as ONE sort at a slice's tile: each slice's group edges add at most a
    sub-tile a group, which this leaves out."""
    tm, _ = row_tile(token_class(min(tokens, GROUPED_TOKENS)) * top_k, group_sizes.shape[-1])
    return rows_multiplied(group_sizes, tm)


def _pass_sizes(starts: jax.Array, ends: jax.Array, base, bound: int) -> jax.Array:
    """The groups' sizes within rows ``[base, base + bound)`` of the sorted
    list, from their first rows and ends ``(..., E)``."""
    lo, hi = np.int32(0), np.int32(bound)
    return lax.sub(lax.clamp(lo, lax.sub(ends, base), hi),
                   lax.clamp(lo, lax.sub(starts, base), hi))


def share_call_sums(chosen: jax.Array, top_k: int, routed: int) -> jax.Array:
    """``(3,) int32`` of grouped calls that hold a share, from the real
    tokens' choices ``chosen (calls, tokens, E)``: the rows the passes handled
    (passes x ``row_bound``), the rows the kernel's dots ran over (the
    sub-tiles each pass's groups touch at the pass's tile) and the passes,
    summed over the calls and over the slices a long call goes by: the
    arithmetic of :func:`_grouped_experts` and of
    ``ExpertMLPs.forward_grouped``, for the serving counters."""
    calls, tokens, E = chosen.shape
    handled = multiplied = passes = jnp.int32(0)
    for at, n in _slices(tokens):
        picks = token_class(n) * top_k
        bound = row_bound(token_class(n), top_k, E, routed)
        tm, _ = row_tile(bound, E)
        sizes = jnp.sum(chosen[:, at: at + n], axis=1, dtype=jnp.int32)     # (calls, E)
        ends = lax.cumsum(sizes, axis=1)
        # (a list of one bound is taken whole, without the loop: one pass whatever fell here)
        ran = (jnp.int32(calls) if bound == picks else
               jnp.sum(lax.div(lax.add(ends[:, E - 1], np.int32(bound - 1)), np.int32(bound))))
        passes = passes + ran
        handled = handled + ran * np.int32(bound)
        # a pass that did not run has no rows within it: nothing to mask
        for p in range(picks // bound):
            multiplied = multiplied + rows_multiplied(
                _pass_sizes(lax.sub(ends, sizes), ends, np.int32(p * bound), bound), tm)
    return jnp.stack([handled, multiplied, passes])


@functools.partial(jax.jit, static_argnames=("top_k", "glu", "dtype", "interpret",
                                              "routed"))
def _grouped_experts(x, combine, live, layer, gate, up, down, *, top_k, glu,
                     dtype, interpret, routed=None):
    """``ExpertMLPs.forward_grouped`` on a whole token class: ``x (T, H)``,
    ``combine (T, E)``, ``live (T,)``, the weight stacks ``(L, E, ...)`` and
    this layer's index. A function of the module, jitted, so that its trace
    (some two hundred equations with the kernels' bodies, a second of Python
    on a serving host) is kept by shape and dtype: a serving cell's 18
    programs (one per insert shape) hold seven token classes between them.

    ``routed``: the width of the router whose choices ``combine`` holds the
    held experts' columns of (None or E: this layer's own). Where only a share
    is held and the list is longer than a tile, :func:`_grouped_share` hands
    on the picks that fell here and no others; else every pick is a row
    (:func:`_grouped_whole`). (The choice is an expression, not an ``if``:
    ``analysis/host_sync.py`` takes keyword-only parameters for traced.)"""
    T, E = combine.shape
    share = routed is not None and routed != E
    bound = row_bound(T, top_k, E, routed)
    return (_grouped_share if bound < T * top_k else _grouped_whole)(
        x, *sort_by_expert(combine, top_k, live, share), live, layer, gate, up, down,
        bound, glu, dtype, interpret, share)


def _grouped_whole(x, weight, order, place, group_sizes, live, layer, gate, up, down,
                   bound, glu, dtype, interpret, share):
    """Every pick of the sorted list a row (``bound`` is all ``T x top_k``):
    a layer that holds every expert, and any list of one tile."""
    (T, top_k), H, E = weight.shape, x.shape[1], group_sizes.shape[0]
    tm, rows = row_tile(bound, E)
    visits = group_visits(group_sizes, rows, tm)
    xs = x.astype(dtype).at[jax.lax.div(order, np.int32(top_k))].get(  # (M, H)
        mode="promise_in_bounds")
    xs = jnp.pad(xs, ((0, rows - T * top_k), (0, 0)))      # to whole m tiles
    # gate and up share the rows and meet in the kernel's last step, on its
    # float32 sums: neither (M, I) product is written out
    a = grouped_matmul(xs, (gate, up) if glu else (gate,), layer, visits, tm,
                       _silu_gated if glu else nn.gelu, interpret=interpret)
    out = grouped_matmul(a, (down,), layer, visits, tm, interpret=interpret)
    # back to the tokens in CHOICE-major order, (top_k, T, H): the leading
    # axis splits for nothing and its float32 sum is one fusion over the
    # gather's rows as they lie. As (T, top_k, H) the few choices sit on the
    # axis the TPU tiles and the array is copied whole into that tiling
    # (112 MiB a Xing layer of an 8 x 512 insert; PERF.md, PR 61)
    out = out.at[place.reshape(T, top_k).T.reshape(T * top_k)].get(
        mode="promise_in_bounds", unique_indices=True).reshape(top_k, T, H)
    # the rows of no group hold whatever the kernel's buffer held, so they are
    # selected away, not scaled (under ``share`` a choice of an absent expert
    # sits in no group either)
    real = (live[None, :] & (weight.T > 0)) if share else live[None, :]
    out = jnp.where(real[:, :, None], out, 0)
    return jnp.einsum("kth,kt->th", out.astype(jnp.float32),
                      weight.T.astype(jnp.float32))


def _grouped_share(x, weight, order, place, group_sizes, live, layer, gate, up, down,
                   bound, glu, dtype, interpret, share=True):
    """The held share of a long sorted list, ``bound`` rows a pass. The sort
    put the picks that fell on held experts FIRST (``n`` of them, a number the
    device alone knows), so a pass's rows are a slice of ``order``: it gathers
    those rows of ``x``, clips the groups to the slice, runs the two kernels
    on ``bound`` rows at ``row_tile(bound, E)`` (the tile sees the rows that
    are there) and adds each token's picks of the slice to the float32 sum.
    ``ceil(n / bound)`` passes: one as a rule, ``M / bound`` where every pick
    fell here (what a layer that holds all pays), none where none did. No
    array of ``T x top_k`` rows of ``H`` is written: on the v5e a Laguna layer
    of a 1 x 4096 insert (40 960 picks, 5 120 real) took 5.33 ms that way and
    takes 2.30 (my chip runs, PR 51; PERF.md section 6 has the forms tried).

    The weighted sum takes a token's picks slot by slot: ``top_k`` gathers of
    ``T`` rows each from the pass's output, a pick that is not in the pass
    selected away (never scaled: its index points at some row of the buffer),
    all added in ONE fusion. XLA fuses no gather into a reduction, so one
    gather of ``(top_k, T, H)`` is written out whole; the slots' read the same
    bytes in pieces and cost the same (2.32 against 2.30 ms). The whole form
    (:func:`_grouped_whole`) reads it so too: its one gather IS written out,
    choice-major, and one fusion sums the leading axis of ``(top_k, T, H)``
    (token-major the compiler copies the gather into ``(T, top_k, H)``'s
    tiling first; its slots taken one by one in Python make two fusions with
    a float32 ``(T, H)`` array between them: PERF.md, PR 61)."""
    T, k = weight.shape
    E = group_sizes.shape[0]
    x = x.astype(dtype)
    tm, rows = row_tile(bound, E)
    ends = lax.cumsum(group_sizes, axis=0)
    starts = lax.sub(ends, group_sizes)
    n = lax.index_in_dim(ends, E - 1, 0, keepdims=False)
    real = live[:, None] & (weight > 0)
    weight = weight.astype(jnp.float32)
    place = place.reshape(T, k)

    def one_pass(p, acc):
        base = lax.mul(p, np.int32(bound))
        visits = group_visits(_pass_sizes(starts, ends, base, bound), rows, tm)
        token = lax.div(lax.dynamic_slice(order, (base,), (bound,)), np.int32(k))
        xs = x.at[token].get(mode="promise_in_bounds")                 # (bound, H)
        xs = jnp.pad(xs, ((0, rows - bound), (0, 0)))
        a = grouped_matmul(xs, (gate, up) if glu else (gate,), layer, visits, tm,
                           _silu_gated if glu else nn.gelu, interpret=interpret)
        out = grouped_matmul(a, (down,), layer, visits, tm, interpret=interpret)
        at = lax.sub(place, base)
        mine = real & (at >= 0) & (at < bound)
        at = lax.select(mine, at, lax.full_like(at, 0))
        for j in range(k):
            pick = out.at[at[:, j]].get(mode="promise_in_bounds").astype(jnp.float32)
            acc = acc + jnp.where(mine[:, j, None], pick * weight[:, j, None], 0)
        return acc

    return lax.fori_loop(np.int32(0), lax.div(lax.add(n, np.int32(bound - 1)), np.int32(bound)),
                         one_pass, jnp.zeros((T, x.shape[1]), jnp.float32))


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
                        stack=None, routed: Optional[int] = None) -> jax.Array:
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

        ``routed``: the width of the router whose choices ``combine`` holds
        the held experts' columns of (None: this layer's own); a row then has
        at most ``top_k`` nonzeros, those that fell elsewhere are nobody's
        here (:func:`sort_by_expert`) and a long call hands on only the picks
        that fell here (:func:`row_bound`)."""
        T, H = x.shape
        if T > GROUPED_TOKENS:
            return jnp.concatenate([
                self.forward_grouped(x[at: at + n], combine[at: at + n], top_k,
                                     None if live is None else live[at: at + n], stack, routed)
                for at, n in _slices(T)])
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
            interpret=kernel_mode.interpret_kernels(), routed=routed)
        return out[:T].astype(x.dtype)

    def __call__(self, x: jax.Array, combine: jax.Array,
                 top_k: Optional[int] = None,
                 live: Optional[jax.Array] = None, stack=None,
                 routed: Optional[int] = None) -> jax.Array:
        # int8 leaves ({"qweight", "scale"}) keep all_experts, whose einsums
        # fuse the dequantisation: no cell serves them
        if self.mode == "grouped" and not isinstance(self.w_gate, Mapping):
            if top_k is None:
                raise ValueError("grouped mode needs the router's top_k")
            return self.forward_grouped(x, combine, top_k, live, stack, routed)
        if self.mode == "capacity_factor":
            return self.forward_capacity_factor(x, combine)
        if self.mode in ("all_experts", "grouped"):
            return self.forward_all_experts(x, combine)
        raise ValueError(f"unknown expert mode {self.mode!r}")
