"""The MoE block (reference ``modules/moe/model.py`` — ``MoE``:7,
``forward``:86: SP exit -> route -> experts -> SP re-entry; aux loss
collection).

The aux (load-balancing) loss is returned through a flax variable collection
``"losses"`` so arbitrarily nested MoE blocks surface it without plumbing
(the reference threads it through return values)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.moe.expert_mlps import ExpertMLPs
from neuronx_distributed_tpu.moe.routing import (
    RouterSinkhorn,
    RouterTopK,
    load_balancing_loss,
    router_z_loss,
)
from neuronx_distributed_tpu.parallel.partitioning import ACT_FULL, ACT_SP, constrain


class MoE(nn.Module):
    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    norm_topk_prob: bool = True        # top_k router: renormalise the kept k
    router: str = "top_k"              # "top_k" | "sinkhorn"
    mode: str = "capacity_factor"      # "capacity_factor" | "all_experts"
    capacity_factor: float = 1.25
    glu: bool = True
    sequence_parallel: bool = False
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    # serving (prefill and decode alike) runs the experts as ONE dropless
    # grouped matmul over the (token, expert) assignments sorted by expert
    # (ExpertMLPs.forward_grouped): only the experts some real token chose
    # are read, none of the assignments is dropped. It takes the place of
    # `capacity_factor`, which would drop (a dropped assignment corrupts the
    # KV cache for the whole generation); a `mode` of "all_experts" is kept
    # as asked, the golden the grouped form is compared with. What the code
    # can see decides the rest: on a mesh with ep > 1 (sorting along the
    # sharded expert axis would all-gather the weights) or tp > 1 (the kernel
    # is not partitioned) serving runs all_experts, as do int8 leaves.
    inference: bool = False
    # the share of a wider expert layer that lives here (expert parallelism
    # seen from ONE chip; models/deepseek_v2.py): the router is
    # ``router_experts`` wide and picks ``top_k`` of ALL of them, this module
    # holds experts ``experts_held_first .. + num_experts`` and computes their
    # part of the result; a pick that fell on an absent expert is dropped
    # before the sort as a dead row's is, and what the absent experts would
    # have added is left out (no exchange, nothing stands in for them). None:
    # every expert is held, today's layer.
    router_experts: Optional[int] = None
    experts_held_first: int = 0
    # DeepSeek's group-limited selection and route scale (moe/routing.py)
    n_group: int = 1
    topk_group: int = 1
    route_scale: float = 1.0
    # how the router scores an expert: "softmax" | "sigmoid" (moe/routing.py)
    scoring_func: str = "softmax"
    # experts that cost nothing (LongCat-Flash's identity experts): the router
    # is ``router_experts + zero_experts`` wide and a token's ``top_k`` fall on
    # real and identity experts alike. An identity expert returns its input,
    # so the chosen weights of the last ``zero_experts`` columns are summed
    # into ONE scalar a token that multiplies the layer's input, in float32,
    # beside the routed result: no weights, no row of the grouped matmul (the
    # picks are dropped before the sort as an absent expert's are) and, under
    # expert parallelism, no exchange: the chip that owns the token adds it.
    zero_experts: int = 0
    # a bias on the scores for the CHOICE only (moe/routing.py)
    selection_bias: bool = False
    # what a router group is scored by: "max" | "top2_sum" (moe/routing.py)
    group_score: str = "max"

    @nn.compact
    def __call__(self, x: jax.Array, live: Optional[jax.Array] = None,
                 stack=None) -> jax.Array:
        """``live`` (b, s) bool, serving only: which tokens are real (a live
        decode row, a prompt's own positions). The rest choose no expert and
        come out zero; None means all are real. ``stack``: the expert weights
        of the whole layer stack and this layer's index, where the layers are
        a scan (``ExpertMLPs.forward_grouped``)."""
        # exit SP: routing needs the full sequence (reference model.py:112-127)
        if self.sequence_parallel:
            x = constrain(x, ACT_FULL)
        b, s, h = x.shape
        if h != self.hidden_size:
            raise ValueError(f"input hidden dim {h} != configured hidden_size {self.hidden_size}")
        flat = x.reshape(b * s, h)

        routed = self.router_experts or self.num_experts
        if (self.zero_experts or self.selection_bias) and self.router != "top_k":
            raise ValueError(f"zero_experts and selection_bias are the top_k router's, "
                             f"not {self.router!r}'s")
        width = routed + self.zero_experts     # what the router chooses among
        share = width != self.num_experts      # only some of those are held (and cost)
        if self.router == "top_k":
            router = RouterTopK(width, top_k=self.top_k,
                                norm_topk_prob=self.norm_topk_prob,
                                n_group=self.n_group, topk_group=self.topk_group,
                                route_scale=self.route_scale,
                                **({} if self.scoring_func == "softmax"
                                   else {"scoring_func": self.scoring_func}),
                                **({"selection_bias": True} if self.selection_bias else {}),
                                **({} if self.group_score == "max"
                                   else {"group_score": self.group_score}),
                                name="router")
        elif self.router == "sinkhorn":
            router = RouterSinkhorn(routed, name="router")
        else:
            raise ValueError(f"unknown router {self.router!r}")
        combine, logits = router(flat)
        picks = combine
        if share:
            combine = jax.lax.slice_in_dim(
                combine, self.experts_held_first,
                self.experts_held_first + self.num_experts, axis=1)
        if self.inference and self.is_mutable_collection("moe_stats"):
            # the (tokens, experts held) choices of the router, whatever `live`
            # says, for the routing counters of the fused session decode and
            # the paged insert (inference/causal_lm.py::_routing_sums); beside
            # them, where only a share is held, each token's picks among ALL
            # the routed experts
            self.sow("moe_stats", "chosen", combine > 0)
            if share:
                self.sow("moe_stats", "routed",
                         jnp.sum(picks > 0, axis=-1, dtype=jnp.int32))
            if self.zero_experts:       # the picks that cost nothing
                self.sow("moe_stats", "zero",
                         jnp.sum(picks[:, routed:] > 0, axis=-1, dtype=jnp.int32))

        mode = self.mode
        if self.inference and mode == "capacity_factor":
            from neuronx_distributed_tpu.parallel import mesh as ps

            on_mesh = ps.model_parallel_is_initialized() and (
                ps.get_expert_model_parallel_size() > 1
                or ps.get_tensor_model_parallel_size() > 1)
            mode = "all_experts" if on_mesh else "grouped"
        experts = ExpertMLPs(
            num_experts=self.num_experts, hidden_size=h,
            intermediate_size=self.intermediate_size, glu=self.glu,
            capacity_factor=self.capacity_factor, mode=mode,
            dtype=self.dtype, param_dtype=self.param_dtype, name="experts",
        )
        out = experts(flat, combine.astype(flat.dtype), top_k=self.top_k,
                      live=None if live is None else live.reshape(b * s),
                      stack=stack, routed=width).reshape(b, s, h)
        if self.zero_experts:
            with jax.named_scope("zero_experts"):
                kept = jnp.sum(picks[:, routed:].astype(jnp.float32), axis=-1)
                if live is not None:        # a token that is not real comes out zero
                    kept = jnp.where(live.reshape(b * s), kept, 0.0)
                out = (out.astype(jnp.float32)
                       + (kept[:, None] * flat.astype(jnp.float32)).reshape(b, s, h)
                       ).astype(out.dtype)

        aux = self.aux_loss_coef * load_balancing_loss(logits, picks, width)
        if self.z_loss_coef:
            aux = aux + self.z_loss_coef * router_z_loss(logits)
        self.sow("losses", "moe_aux_loss", aux)

        # re-enter SP (reference model.py:128-147)
        if self.sequence_parallel:
            out = constrain(out, ACT_SP)
        return out


def collect_aux_losses(variables) -> jax.Array:
    """Sum every sown ``moe_aux_loss`` (over layers); 0 if none."""
    losses = variables.get("losses", {})
    total = jnp.zeros((), jnp.float32)

    def walk(tree):
        nonlocal total
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v)
            else:  # sown values are tuples of arrays
                for leaf in (v if isinstance(v, (tuple, list)) else (v,)):
                    total = total + jnp.sum(leaf)

    walk(losses)
    return total
