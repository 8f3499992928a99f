"""Operations and bytes a stack of window and full attention layers with a
held share of its experts NEEDS, from its published sizes
(``configs/laguna-s-2.1.json``'s key names).

Needed, not executed, as ``opcount.py`` counts: a live step reads every weight
it multiplies once (the experts some live row chose, not all that are held;
the head, not the embedding table), the cached keys and values of its live
rows as far as each reaches in a full layer and the last ``sliding_window`` of
them in a window layer (the program reads whole chunks, whole rings and the
rows of a rung); a prompt multiplies its REAL tokens, a window layer's
attention over the band and not the triangle, the held picks only, the head at
its last position. Each is a lower bound on what the program moves or
multiplies, so a share of a peak built on it cannot pass 100 %.

One multiply-add is two operations; weights and cache at ``bytes_per_el`` (2:
bf16).
"""

from __future__ import annotations

from typing import Iterable, Optional

FULL, SLIDING = "full_attention", "sliding_attention"


def window_config(record: dict) -> Optional[dict]:
    """A record's configuration if it has window layers, else None (what the
    ``swa.*`` readers ask first: silent on every other configuration's record)."""
    cfg = record.get("config") or {}
    return cfg if SLIDING in cfg.get("layer_types", ()) else None


def layer_kinds(cfg: dict) -> list:
    """``(kind, dense?)`` of the layers as run: the first ``num_hidden_layers``."""
    n = cfg["num_hidden_layers"]
    dense = set(cfg.get("mlp_only_layers", ()))
    return [(kind, l in dense) for l, kind in enumerate(cfg["layer_types"][:n])]


def layers_of(cfg: dict, kind: str) -> int:
    return sum(k == kind for k, _ in layer_kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in layer_kinds(cfg))


def heads(cfg: dict, kind: str) -> int:
    return next(h for h, t in zip(cfg["num_attention_heads_per_layer"], cfg["layer_types"])
                if t == kind)


def attention_params(cfg: dict, kind: str) -> int:
    """Wq and Wo at the kind's heads, Wk and Wv at the KV heads, the gate."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    n, nkv = heads(cfg, kind), cfg["num_key_value_heads"]
    return 2 * h * n * d + 2 * h * nkv * d + h * n


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg.get("router_experts", cfg["num_experts"])


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_params(cfg: dict, kind: str, dense: bool, experts: float) -> float:
    """A layer of ``kind`` with ``experts`` of its routed experts counted:
    attention, two norms, and the dense MLP or the shared expert, the whole
    router and those experts."""
    ffn = dense_mlp_params(cfg) if dense else (
        shared_params(cfg) + router_params(cfg) + experts * expert_params(cfg))
    return attention_params(cfg, kind) + 2 * cfg["hidden_size"] + ffn


def total_params(cfg: dict) -> int:
    """Every parameter HELD: the layers with ``num_experts`` experts each, the
    final norm, the embedding and the untied head."""
    return int(sum(layer_params(cfg, kind, dense, cfg["num_experts"])
                   for kind, dense in layer_kinds(cfg))
               + cfg["hidden_size"] + 2 * head_params(cfg))


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """K and V of one token in ONE layer (both kinds cache the same heads)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el


# ------------------------------------------------------------------- decode

def decode_step_bytes(cfg: dict, rows: float, full_tokens: float, window_tokens: float,
                      experts_read: float, bytes_per_el: int = 2) -> float:
    """Bytes a step of ``rows`` live rows must move: the weights it multiplies
    (``experts_read`` experts an expert layer, the head; the embedding's rows
    of the live tokens only), the cached tokens of the live rows summed over
    them: ``full_tokens`` a full layer, ``window_tokens`` a window layer."""
    weights = sum(layer_params(cfg, kind, dense, experts_read)
                  for kind, dense in layer_kinds(cfg))
    weights += cfg["hidden_size"] + head_params(cfg) + rows * cfg["hidden_size"]
    cached = (layers_of(cfg, FULL) * full_tokens + layers_of(cfg, SLIDING) * window_tokens)
    return weights * bytes_per_el + cached * kv_bytes_per_token(cfg, bytes_per_el)


def decode_step_flops(cfg: dict, rows: float, full_tokens: float, window_tokens: float,
                      assignments: float) -> float:
    """Each live row's token through every weight outside the routed experts
    and the head, ``assignments`` (token, held expert) pairs through an expert
    each (summed over the layers), q.k and p.v over the cached tokens."""
    per_row = sum(layer_params(cfg, kind, dense, 0) for kind, dense in layer_kinds(cfg))
    per_row += head_params(cfg)
    d = cfg["head_dim"]
    cached = 2 * 2 * d * (layers_of(cfg, FULL) * heads(cfg, FULL) * full_tokens
                          + layers_of(cfg, SLIDING) * heads(cfg, SLIDING) * window_tokens)
    return 2 * rows * per_row + 2 * assignments * expert_params(cfg) + cached


def decode_step_roofline_s(cfg: dict, rows: float, full_tokens: float, window_tokens: float,
                           experts_read: float, assignments: float, peaks: dict) -> float:
    """The least time the whole live step can take on this chip: the larger of
    its bytes over the HBM's rate and its operations over the bf16 peak."""
    return max(decode_step_bytes(cfg, rows, full_tokens, window_tokens, experts_read)
               / peaks["hbm_bytes_per_s"],
               decode_step_flops(cfg, rows, full_tokens, window_tokens, assignments)
               / peaks["bf16_flops_per_s"])


def window_tokens_of(blocks: Iterable[Iterable[tuple]], window: int) -> float:
    """Cached tokens a window layer needs, summed over the live row-steps of
    ``blocks`` (``decode_steps.blocks_by_stamp``'s entries: a row live for
    ``c`` steps from context ``x`` needs ``min(x + t, window)`` at step ``t``)."""
    return float(sum(min(x + t, window) for rows in blocks for c, x in rows for t in range(c)))


# ------------------------------------------------------------------ prefill

def band_pairs(s: int, window: Optional[int]) -> float:
    """(query, key) pairs a causal mask leaves of ``s`` tokens: the triangle,
    or under a window the band of the token and the ``window - 1`` before it."""
    if window is None or s <= window:
        return s * (s + 1) / 2
    return window * (window + 1) / 2 + (s - window) * window


def insert_flops(cfg: dict, prompt_lens: Iterable[int], assignments_per_token: float) -> float:
    """FLOPs to prefill these prompts' REAL tokens and give one row of logits
    each: every weight outside the routed experts a token, an expert for each
    of ``assignments_per_token`` held picks (summed over the layers), causal
    attention over the triangle in the full layers and the band in the window
    layers, the head once a prompt."""
    per_token = sum(layer_params(cfg, kind, dense, 0) for kind, dense in layer_kinds(cfg))
    per_token += assignments_per_token * expert_params(cfg)
    d, w = cfg["head_dim"], cfg["sliding_window"]
    total = 0.0
    for s in prompt_lens:
        total += 2 * s * per_token + 2 * head_params(cfg)
        total += 2 * 2 * d * (layers_of(cfg, FULL) * heads(cfg, FULL) * band_pairs(s, None)
                              + layers_of(cfg, SLIDING) * heads(cfg, SLIDING) * band_pairs(s, w))
    return total


# ------------------------------------------------- the windowed flash call

def flash_window_flops(cfg: dict, rows: int, bucket: int) -> float:
    """One window layer's ``flash_fwd_window`` call over ``rows`` prompts padded
    to ``bucket``: q.k and p.v over the band (the kernel runs the padding too)."""
    return (rows * 2 * 2 * cfg["head_dim"] * heads(cfg, SLIDING)
            * band_pairs(bucket, cfg["sliding_window"]))


def flash_window_bytes(cfg: dict, rows: int, bucket: int, bytes_per_el: int = 2) -> float:
    """The same call's q read and o written at the window layers' heads, k and
    v read once at the KV heads."""
    d = cfg["head_dim"]
    return rows * bucket * d * bytes_per_el * (2 * heads(cfg, SLIDING)
                                               + 2 * cfg["num_key_value_heads"])
