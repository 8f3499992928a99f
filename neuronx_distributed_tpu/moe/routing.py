"""MoE routers (reference ``modules/moe/routing.py`` — ``RouterBase``:9,
``RouterTopK``:89, ``RouterSinkhorn``:123, fixed-iteration ``_sinkhorn``:186).

Routing math runs in fp32 (the reference leans on fp64 via XLA_DOWNCAST
tricks for its mask arithmetic — SURVEY §7.3; here all integer bookkeeping is
int32, which is exact, and only probabilities are float)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.parallel.layers import default_kernel_init


class RouterTopK(nn.Module):
    """Softmax top-k router. Returns (combine_weights, logits) where
    ``combine_weights`` is (T, E) with exactly ``top_k`` nonzeros per row,
    renormalized to sum 1 (reference RouterTopK, routing.py:89-121) unless
    ``norm_topk_prob`` is off (HF's key: OLMoE keeps the chosen softmax
    probabilities as they are, so a row sums to less than one).

    ``n_group > 1`` is DeepSeek-V2's ``group_limited_greedy``: the experts are
    ``n_group`` groups of consecutive experts, a group scores the largest
    probability among its own, the ``topk_group`` best groups stay and the
    top-k is taken inside them. ``route_scale`` multiplies the weights
    (``routed_scaling_factor``). The scores are the softmax's, in float32,
    whatever the selection.

    ``scoring_func="sigmoid"`` scores each expert by ``sigmoid(logit)`` on its
    own (DeepSeek-V3's router, without its selection bias): the top-k by
    score, their scores renormalised (``norm_topk_prob``) and scaled. The
    selection, the groups and the weights read ``scores`` wherever the
    softmax's probabilities stood.

    ``selection_bias`` adds a parameter ``e_score_correction_bias``, one
    float32 an expert, to the scores for the CHOICE only (LongCat-Flash's and
    DeepSeek-V3's load-balancing bias): the top-k is taken of ``scores +
    bias``, the weights stay the scores themselves. Not with groups (a
    group's score would have to say which of the two it reads)."""

    num_experts: int
    top_k: int = 2
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    route_scale: float = 1.0
    scoring_func: str = "softmax"      # | "sigmoid"
    selection_bias: bool = False
    group_score: str = "max"           # | "top2_sum"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {self.scoring_func!r}: 'softmax' or 'sigmoid'")
        if self.group_score not in ("max", "top2_sum"):
            raise ValueError(f"group_score {self.group_score!r}: 'max' or 'top2_sum'")
        biased_groups = self.selection_bias and self.n_group > 1
        if biased_groups and self.group_score != "top2_sum":
            raise ValueError("selection_bias with n_group > 1 needs group_score='top2_sum': "
                             "a group scored by its max would have to choose between the "
                             "score and score + bias")
        # router weight is replicated (the reference's LinearRouter with
        # weight-grad all-reduce, moe_parallel_layers.py:348)
        w = self.param("kernel", default_kernel_init, (x.shape[-1], self.num_experts),
                       self.param_dtype)
        logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
        probs = (jax.nn.sigmoid(logits) if self.scoring_func == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        eligible = probs
        if biased_groups:
            with jax.named_scope("router_bias"):
                choice = probs + self.param("e_score_correction_bias", nn.initializers.zeros,
                                            (self.num_experts,), jnp.float32)
            with jax.named_scope("router_groups"):
                keep = group_limit(choice, self.n_group, self.topk_group, self.group_score)
                eligible = jnp.where(keep > 0, choice, -jnp.inf)
        elif self.n_group > 1:
            with jax.named_scope("router_groups"):
                eligible = probs * group_limit(probs, self.n_group, self.topk_group,
                                               self.group_score)
        elif self.selection_bias:
            with jax.named_scope("router_bias"):
                eligible = probs + self.param("e_score_correction_bias", nn.initializers.zeros,
                                              (self.num_experts,), jnp.float32)
        topv, topi = jax.lax.top_k(eligible, self.top_k)
        mask = jnp.sum(jax.nn.one_hot(topi, self.num_experts, dtype=probs.dtype), axis=-2)
        gates = probs * mask
        if self.norm_topk_prob:
            denom = jnp.sum(gates, axis=-1, keepdims=True)
            gates = gates / jnp.maximum(denom, 1e-9)
        if self.route_scale != 1.0:
            gates = gates * self.route_scale
        return gates, logits


def group_limit(probs: jax.Array, n_group: int, topk_group: int,
                score: str = "max") -> jax.Array:
    """``(T, E)`` mask, one over the experts of each token's ``topk_group``
    best groups (a group's score: its largest probability, or with
    ``score="top2_sum"`` the sum of its two largest; a tie goes to the
    lower group, as ``lax.top_k`` breaks it) and zero over the rest."""
    T, E = probs.shape
    grouped = probs.reshape(T, n_group, E // n_group)
    if score == "top2_sum":
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    else:
        best = jnp.max(grouped, axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)
    keep = jnp.sum(jax.nn.one_hot(groups, n_group, dtype=probs.dtype), axis=-2)
    return jnp.repeat(keep, E // n_group, axis=-1)


class RouterSinkhorn(nn.Module):
    """Top-1 Sinkhorn-balanced router with a FIXED iteration count so the
    graph stays static (reference RouterSinkhorn, routing.py:123-218)."""

    num_experts: int
    num_iterations: int = 3
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        w = self.param("kernel", default_kernel_init, (x.shape[-1], self.num_experts),
                       self.param_dtype)
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)

        # sinkhorn balancing on the assignment matrix (training-time only;
        # gradients flow through the softmax gate, not the balancing)
        cost = jax.lax.stop_gradient(logits)
        # max-subtract before exp (overflow-safe; invariant under the
        # row/column normalizations below)
        pi = jnp.exp(cost - jnp.max(cost, axis=-1, keepdims=True))
        for _ in range(self.num_iterations):
            pi = pi / jnp.maximum(jnp.sum(pi, axis=0, keepdims=True), 1e-9)  # col balance
            pi = pi / jnp.maximum(jnp.sum(pi, axis=1, keepdims=True), 1e-9)  # row norm
        top1 = jnp.argmax(pi, axis=-1)
        mask = jax.nn.one_hot(top1, self.num_experts, dtype=jnp.float32)
        gate = jnp.sum(jax.nn.softmax(logits, axis=-1) * mask, axis=-1, keepdims=True)
        return mask * gate, logits


def load_balancing_loss(logits: jax.Array, combine: jax.Array, num_experts: int) -> jax.Array:
    """Switch-Transformer aux loss (reference ``moe/loss_function.py:5``):
    ``E * sum_e f_e * p_e`` with f = fraction of tokens dispatched to e and
    p = mean router prob for e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatched = (combine > 0).astype(jnp.float32)
    f = jnp.mean(dispatched, axis=0)          # (E,)
    p = jnp.mean(probs, axis=0)               # (E,)
    return num_experts * jnp.sum(f * p)


def router_z_loss(logits: jax.Array) -> jax.Array:
    """ST-MoE z-loss — stabilizes router logits (extension beyond the
    reference's loss set; off by default in the MoE layer)."""
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z**2)
