"""Operations and bytes a stack of latent-attention and expert layers under
several residual streams NEEDS, from its published sizes
(``configs/xing4.0-29b-a4b.json``'s key names): DeepSeek-V2's layer
(``opcount_latent.py``) with every routed expert held, plus, a SUB-BLOCK (two a
layer), the stream mix's one projection: ``hc_mult x hidden_size`` rows by
``hc_mult (hc_mult + 2)`` columns.

Needed, not executed, as ``opcount.py`` counts: a live step reads every weight
it multiplies once (the experts some live row chose, not all 64; the head, not
the embedding table; the mixes' ``phi``) and the cached latent of its live
rows' tokens; a prompt multiplies its real tokens, ``num_experts_per_tok``
experts a token, the head at its last position. The streams themselves (four
times the hidden state read and written a sub-block) and the Sinkhorn's few
hundred operations a token are NOT counted: a program could keep the first in
fast memory, and the second is no matrix product. Each count is a lower bound
on what the program moves or multiplies, so a share of a peak built on it
cannot pass 100 %, whoever does the moving.

One multiply-add is two operations; weights and cache at ``bytes_per_el`` (2:
bf16).
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

SUB_BLOCKS = 2      # stream mixes a layer


def mhc_config(record: dict) -> Optional[dict]:
    """A record's configuration if it carries several residual streams, else
    None (what the ``mhc.*`` readers ask first: silent on every other record)."""
    cfg = record.get("config") or {}
    return cfg if cfg.get("hc_mult") and "hc_sinkhorn_iters" in cfg else None


def mix_projection_params(cfg: dict) -> int:
    """``phi`` of ONE sub-block's mix: the flattened streams by the columns of
    ``[H_pre | H_post | H_res]``."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * n * (n + 2)


def mix_params(cfg: dict) -> int:
    """One sub-block's mix: ``phi``, three scalars and the biases."""
    n = cfg["hc_mult"]
    return mix_projection_params(cfg) + 3 + n * (n + 2)


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) + dense_mlp_params(cfg) + SUB_BLOCKS * mix_params(cfg)


def expert_layer_params(cfg: dict) -> int:
    """One expert layer whole (norms left out)."""
    return (attention_params(cfg) + shared_params(cfg) + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg) + SUB_BLOCKS * mix_params(cfg))


def total_params(cfg: dict) -> int:
    """Layers + embedding + untied head."""
    dense, expert = layers(cfg)
    return (dense * dense_layer_params(cfg) + expert * expert_layer_params(cfg)
            + 2 * head_params(cfg))


def token_params(cfg: dict, experts: float) -> float:
    """The weights ONE token of the stack multiplies, ``experts`` routed
    experts counted a layer that has them (norms, ``alpha`` and ``beta`` left
    out, the head apart)."""
    dense, expert = layers(cfg)
    return (cfg["num_hidden_layers"] * (attention_params(cfg)
                                        + SUB_BLOCKS * mix_projection_params(cfg))
            + dense * dense_mlp_params(cfg)
            + expert * (shared_params(cfg) + router_params(cfg) + experts * expert_params(cfg)))


def decode_step_bytes(cfg: dict, rows: float, cached_tokens: float, experts_read: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step must move: MLA's five matrices and the mix's
    projection of both sub-blocks of every layer, the dense layers' MLP, per
    expert layer the shared expert, the router and the ``experts_read`` experts
    a live layer-step READ (``moe_experts_touched / moe_layer_steps``), the
    head once, and the cached latent of ``cached_tokens`` tokens (summed over
    the live rows) in every layer. ``rows`` is not in the count: a weight is
    read once whatever the rows (they are in ``experts_read`` and
    ``cached_tokens``), and the rows' streams are not counted (above)."""
    experts_read = min(float(experts_read), cfg["n_routed_experts"])
    weights = token_params(cfg, experts_read) + head_params(cfg)
    cache = cached_tokens * cfg["num_hidden_layers"] * latent_bytes_per_token_layer(
        cfg, bytes_per_el)
    return weights * bytes_per_el + cache


def insert_flops(cfg: dict, prompt_lens: Iterable[int]) -> float:
    """FLOPs to prefill these prompts' REAL tokens and give one row of logits
    each: every weight outside the experts a token (the mixes' projections
    among them: ``2 x hc_mult x hidden_size x hc_mult (hc_mult + 2)`` a token a
    sub-block), ``num_experts_per_tok`` experts a token an expert layer, causal
    attention over the triangle at ``[nope | rope]`` for q.k and ``v_head_dim``
    for p.v, the head once a prompt."""
    per_token = token_params(cfg, cfg["num_experts_per_tok"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pair = 2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
    total = 0.0
    for s in prompt_lens:
        total += 2 * s * per_token + 2 * head_params(cfg)
        total += cfg["num_hidden_layers"] * pair * s * (s + 1) / 2
    return total
