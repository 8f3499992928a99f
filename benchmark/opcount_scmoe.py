"""Operations and bytes a shortcut-connected block over latent attention NEEDS,
from its published sizes (``configs/longcat-flash-chat.json``'s key names):
one layer is TWO latent attentions, TWO dense gated MLPs of ``ffn_hidden_size``
and ONE expert layer whose router is ``router_experts + zero_expert_num`` wide.

Needed, not executed, as ``opcount.py`` counts: a live step reads every weight
it multiplies once (the experts some live row chose AND that are held, not all
that are held; the head, not the embedding table) and the cached latent of its
live rows' tokens in every SUB-layer; a prompt multiplies its real tokens, the
held picks only, the head at its last position. A pick that fell on an identity
expert moves no weight and multiplies nothing: it is in no count here. Each is
a lower bound on what the program moves or multiplies, so a share of a peak
built on it cannot pass 100 %.

``n_routed_experts`` counts the real experts HELD. One multiply-add is two
operations; weights and cache at ``bytes_per_el`` (2: bf16).
"""

from __future__ import annotations

from typing import Iterable, Optional

# ONE latent attention's five matrices, the head, and what a token leaves in
# one cache layer are DeepSeek-V2's counts under the same published keys
from benchmark.opcount_latent import attention_params, head_params
from benchmark.opcount_latent import latent_bytes_per_token_layer as latent_bytes_per_token_sub_layer

SUB_LAYERS = 2


def scmoe_config(record: dict) -> Optional[dict]:
    """A record's configuration if its router has identity experts, else None
    (what the ``scmoe.*`` readers ask first: silent on every other record)."""
    cfg = record.get("config") or {}
    return cfg if "zero_expert_num" in cfg and "ffn_hidden_size" in cfg else None


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_width(cfg: dict) -> int:
    return cfg.get("router_experts", cfg["n_routed_experts"]) + cfg["zero_expert_num"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)


def layer_params(cfg: dict, experts: float) -> float:
    """One layer with ``experts`` of its real experts counted (norms left out)."""
    return (SUB_LAYERS * (attention_params(cfg) + dense_mlp_params(cfg)) + router_params(cfg)
            + experts * expert_params(cfg))


def total_params(cfg: dict) -> int:
    """Layers as held + embedding + untied head."""
    return int(cfg["num_layers"] * layer_params(cfg, cfg["n_routed_experts"])
               + 2 * head_params(cfg))


def decode_step_bytes(cfg: dict, context_tokens: float, experts_read: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step must move: every layer's weights outside the
    experts, the ``experts_read`` real experts a live layer-step READ
    (``moe_experts_touched / moe_layer_steps``), the head once, and the cached
    latent of ``context_tokens`` tokens (summed over the live rows) in every
    sub-layer."""
    experts_read = min(float(experts_read), cfg["n_routed_experts"])
    weights = cfg["num_layers"] * layer_params(cfg, experts_read) + head_params(cfg)
    cache = (context_tokens * SUB_LAYERS * cfg["num_layers"]
             * latent_bytes_per_token_sub_layer(cfg, bytes_per_el))
    return weights * bytes_per_el + cache


def insert_flops(cfg: dict, prompt_lens: Iterable[int], held_picks_per_token: float) -> float:
    """FLOPs to prefill these prompts' REAL tokens and give one row of logits
    each: every weight outside the experts a token, an expert for each of
    ``held_picks_per_token`` picks that fell on a held expert (summed over the
    layers; an identity pick or one of an absent expert costs nothing), causal
    attention over the triangle at ``[nope | rope]`` for q.k and ``v_head_dim``
    for p.v in every sub-layer, the head once a prompt."""
    per_token = cfg["num_layers"] * layer_params(cfg, 0) + held_picks_per_token * expert_params(cfg)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pair = 2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
    total = 0.0
    for s in prompt_lens:
        total += 2 * s * per_token + 2 * head_params(cfg)
        total += SUB_LAYERS * cfg["num_layers"] * pair * s * (s + 1) / 2
    return total
