"""Operations and bytes a latent attention with a lightning indexer beside it
NEEDS, from its published sizes (``configs/deepseek-v3.2.json``'s key names):
DeepSeek-V2's layer (``opcount_latent.py``) plus, a layer, the indexer's three
matrices, an index key of ``index_head_dim`` a cached token, and an attention
that reads ``index_topk`` of a row's tokens and no more.

Needed, not executed, as ``opcount.py`` counts: a live step reads every weight
it multiplies once (the experts some live row chose AND that are held; the
head, not the embedding table), the index key of every token VISIBLE to a live
row (each is scored) and the latent of every CHOSEN token (``min(reach,
index_topk)`` a row), in every layer; a prompt multiplies its real tokens, the
held picks only, scores the pairs of its queries past ``index_topk`` (below it
nothing has to be scored: every visible token is chosen), attends over chosen
pairs only, the head at its last position. A program that reads the whole
extent under a mask, or scores what it need not, moves and multiplies more:
each count is a lower bound, so a share of a peak built on it cannot pass
100 %, whoever does the moving.

``n_routed_experts`` counts the experts HELD. One multiply-add is two
operations; weights and cache at ``bytes_per_el`` (2: bf16).
"""

from __future__ import annotations

from typing import Iterable, Optional

# MLA's five matrices, the MLPs, the router, the head and what a token's latent
# takes are DeepSeek-V2's counts under the same published keys
from benchmark.opcount_latent import (
    attention_params,
    dense_mlp_params,
    expert_params,
    head_params,
    latent_bytes_per_token_layer,
    layers,
    router_params,
    shared_params,
)


def sparse_config(record: dict) -> Optional[dict]:
    """A record's configuration if its attention has an indexer, else None
    (what the ``dsa.*`` readers ask first: silent on every other record)."""
    cfg = record.get("config") or {}
    return cfg if cfg.get("index_topk") and "index_head_dim" in cfg else None


def indexer_params(cfg: dict) -> int:
    """The indexer's three matrices of one layer: queries out of MLA's query
    latent, one key a token and a weight a head out of the layer's input."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * heads * dim + cfg["hidden_size"] * (dim + heads)


def index_key_bytes_per_token_layer(cfg: dict, bytes_per_el: int = 2) -> int:
    return cfg["index_head_dim"] * bytes_per_el


def token_params(cfg: dict, experts: float) -> float:
    """The weights ONE token of the stack multiplies, ``experts`` routed
    experts counted a layer that has them (norms left out, the head apart)."""
    dense, expert = layers(cfg)
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + indexer_params(cfg))
            + dense * dense_mlp_params(cfg)
            + expert * (shared_params(cfg) + router_params(cfg) + experts * expert_params(cfg)))


def total_params(cfg: dict) -> int:
    """Layers as held + embedding + untied head."""
    return int(token_params(cfg, cfg["n_routed_experts"]) + 2 * head_params(cfg))


def decode_step_bytes(cfg: dict, visible_tokens: float, chosen_tokens: float,
                      experts_read: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step must move: every layer's weights outside the
    experts (the indexer's among them), the ``experts_read`` experts a live
    layer-step READ (``moe_experts_touched / moe_layer_steps``), the head once,
    the index key of ``visible_tokens`` tokens and the latent of
    ``chosen_tokens`` tokens (both summed over the live rows) in every layer."""
    experts_read = min(float(experts_read), cfg["n_routed_experts"])
    weights = token_params(cfg, experts_read) + head_params(cfg)
    cache = cfg["num_hidden_layers"] * (
        visible_tokens * index_key_bytes_per_token_layer(cfg, bytes_per_el)
        + chosen_tokens * latent_bytes_per_token_layer(cfg, bytes_per_el))
    return weights * bytes_per_el + cache


def insert_flops(cfg: dict, prompt_lens: Iterable[int], held_picks_per_token: float) -> float:
    """FLOPs to prefill these prompts' REAL tokens and give one row of logits
    each: every weight outside the experts a token, an expert for each of
    ``held_picks_per_token`` picks that fell on a held expert (summed over the
    layers), ``2 x index_n_heads x index_head_dim`` a scored pair (a query at
    position ``index_topk`` or later against every token visible to it),
    attention at ``[nope | rope]`` for q.k and ``v_head_dim`` for p.v over the
    CHOSEN pairs (``min(position + 1, index_topk)`` a query), the head once a
    prompt."""
    per_token = token_params(cfg, 0) + held_picks_per_token * expert_params(cfg)
    k = cfg["index_topk"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pair = 2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
    score = 2 * cfg["index_n_heads"] * cfg["index_head_dim"]
    total = 0.0
    for s in prompt_lens:
        below = min(s, k)
        triangle, head = s * (s + 1) / 2, below * (below + 1) / 2
        total += 2 * s * per_token + 2 * head_params(cfg)
        total += cfg["num_hidden_layers"] * (pair * (head + (s - below) * k)
                                             + score * (triangle - head))
    return total
