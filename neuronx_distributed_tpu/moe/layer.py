"""The MoE block (reference ``modules/moe/model.py`` — ``MoE``:7,
``forward``:86: SP exit -> route -> experts -> SP re-entry; aux loss
collection).

The aux (load-balancing) loss is returned through a flax variable collection
``"losses"`` so arbitrarily nested MoE blocks surface it without plumbing
(the reference threads it through return values)."""

from __future__ import annotations

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
    # inference dispatch (reference expert_mlps.py:297 forward): token-gen
    # steps (seq==1) use selective loading when T*top_k/E is below the
    # threshold, else all_experts; context encoding keeps `mode`, and never
    # drops (capacity_factor -> all_experts). At Mixtral's 8 experts top-2
    # that is all-experts from 2 rows up, which costs nothing: 4 rows touch
    # every expert. At OLMoE's 64 experts top-8 it is all-experts from 4 rows
    # up too (4 * 8 / 64 = 0.5), where 8 rows choose at most 64 and about 3
    # live rows about 21 of the 64 it reads; and every prefill does
    # E / top_k = 8 x the expert FLOPs the routing needs (Mixtral: 4 x).
    # ROADMAP S4 replaces the rule; `olmoe-1b-7b.chat` shows its cost.
    inference: bool = False
    selective_loading_threshold: float = 0.5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # exit SP: routing needs the full sequence (reference model.py:112-127)
        if self.sequence_parallel:
            x = constrain(x, ACT_FULL)
        b, s, h = x.shape
        if h != self.hidden_size:
            raise ValueError(f"input hidden dim {h} != configured hidden_size {self.hidden_size}")
        flat = x.reshape(b * s, h)

        if self.router == "top_k":
            router = RouterTopK(self.num_experts, top_k=self.top_k,
                                norm_topk_prob=self.norm_topk_prob, name="router")
        elif self.router == "sinkhorn":
            router = RouterSinkhorn(self.num_experts, name="router")
        else:
            raise ValueError(f"unknown router {self.router!r}")
        combine, logits = router(flat)
        if self.inference and s == 1 and self.is_mutable_collection("moe_stats"):
            # the (rows, experts) choices of a decode step, for the fused
            # session decode's routing counter (inference/causal_lm.py)
            self.sow("moe_stats", "chosen", combine > 0)

        mode = self.mode
        if self.inference:
            from neuronx_distributed_tpu.parallel import mesh as ps

            ep = (ps.get_expert_model_parallel_size()
                  if ps.model_parallel_is_initialized() else 1)
            if s == 1:  # token generation (static shapes)
                tokens = b * s
                use_selective = (
                    tokens * self.top_k / self.num_experts
                    < self.selective_loading_threshold
                    # selective gathers along the EP-sharded expert axis, which
                    # GSPMD would service by all-gathering ALL expert weights —
                    # defeating the point (the reference likewise excludes EP
                    # from token-gen inference, SURVEY §2.3)
                    and ep == 1
                )
                mode = "selective" if use_selective else "all_experts"
            elif mode == "capacity_factor":
                # context encoding must not drop tokens: a dropped assignment
                # would corrupt the KV cache for the whole generation. The
                # reference's serving configs run full capacity for the same
                # reason (capacity_factor=None -> all_experts).
                mode = "all_experts"
        experts = ExpertMLPs(
            num_experts=self.num_experts, hidden_size=h,
            intermediate_size=self.intermediate_size, glu=self.glu,
            capacity_factor=self.capacity_factor, mode=mode,
            dtype=self.dtype, param_dtype=self.param_dtype, name="experts",
        )
        out = experts(flat, combine.astype(flat.dtype),
                      top_k=self.top_k).reshape(b, s, h)

        aux = self.aux_loss_coef * load_balancing_loss(logits, combine, self.num_experts)
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
