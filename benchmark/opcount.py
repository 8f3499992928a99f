"""Operations and bytes a configuration NEEDS, from its published sizes.

Needed, not executed: a prompt token needs its top-k experts, not all of
them; a causal query needs the keys before it, not the padded bucket; only a
prompt's last position needs the output head when one token is asked for.
Work the program does beyond this (padding, all-experts prefill, gathered
slabs, recomputation) lowers its roofline share and its MFU, as it should.

``cfg`` is a configuration file's dict (``configs/<name>.json``), in the
published key names. One multiply-add is two operations. Weights and cache
are counted at ``bytes_per_el`` (2: bf16).
"""

from __future__ import annotations

from typing import Iterable, Optional


def _heads(cfg: dict):
    n = cfg["num_attention_heads"]
    return n, cfg.get("num_key_value_heads", n), cfg.get("head_dim", cfg["hidden_size"] // n)


def attention_params(cfg: dict) -> int:
    """q, k, v, o projection weights of one layer (biases left out: < 0.01 %)."""
    n, nkv, d = _heads(cfg)
    h = cfg["hidden_size"]
    return h * n * d + 2 * h * nkv * d + n * d * h


def ffn_matrices(cfg: dict) -> int:
    """3 for a gated (SwiGLU) feed-forward, 2 for GPT-NeoX's plain GELU MLP."""
    return 2 if "rotary_pct" in cfg else 3


def expert_params(cfg: dict) -> int:
    """One expert, or the one dense feed-forward, of one layer."""
    return ffn_matrices(cfg) * cfg["hidden_size"] * cfg["intermediate_size"]


def experts(cfg: dict) -> tuple:
    """(experts held, experts a token uses); (1, 1) for a dense model."""
    return cfg.get("num_local_experts", 1), cfg.get("num_experts_per_tok", 1)


def router_params(cfg: dict) -> int:
    e, _ = experts(cfg)
    return cfg["hidden_size"] * e if e > 1 else 0


def layer_params(cfg: dict) -> int:
    e, _ = experts(cfg)
    return attention_params(cfg) + router_params(cfg) + e * expert_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Layers + embedding + untied head (norms and biases left out)."""
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * head_params(cfg)


def active_layer_params(cfg: dict) -> int:
    """Weights of one layer that ONE token's forward multiplies by."""
    _, k = experts(cfg)
    return attention_params(cfg) + router_params(cfg) + k * expert_params(cfg)


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    _, nkv, d = _heads(cfg)
    return cfg["num_hidden_layers"] * 2 * nkv * d * bytes_per_el


# ------------------------------------------------------------------- decode

def decode_step_bytes(cfg: dict, rows: float, context_tokens: float,
                      bytes_per_el: int = 2, experts_read: Optional[float] = None) -> float:
    """Bytes one decode step over ``rows`` streams must read: every layer's
    attention and router weights, the experts of a layer the step READ
    (``experts_read``: ``moe_experts_touched`` a live layer-step, which the
    program counts since PR 26), the output head, and the cached keys and
    values of ``context_tokens`` tokens (summed over the rows). Without
    ``experts_read`` the experts are those the rows' tokens CAN choose,
    ``min(experts, rows * k)`` - all 8 of Mixtral's from 4 rows up: an upper
    bound, since two rows often choose the same expert (the fallback for a
    record without the counter, and the dense models' one feed-forward)."""
    e, k = experts(cfg)
    if experts_read is None or e == 1:
        experts_read = min(e, max(rows, 1.0) * k)
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + min(e, experts_read) * expert_params(cfg))
    weights = cfg["num_hidden_layers"] * per_layer + head_params(cfg)
    return weights * bytes_per_el + context_tokens * kv_bytes_per_token(cfg, bytes_per_el)


# ------------------------------------------------------------------ prefill

def attention_flops(cfg: dict, seq: int) -> float:
    """Causal attention of one sequence in one layer: q.k and p.v over the
    seq*(seq+1)/2 (query, key) pairs that the mask keeps."""
    n, _, d = _heads(cfg)
    return 2 * 2 * n * d * seq * (seq + 1) / 2


def prefill_flops(cfg: dict, prompt_lens: Iterable[int], head_positions: int = 1) -> float:
    """FLOPs to prefill these prompts and produce ``head_positions`` logits
    rows each (1: the next token): top-k experts per token."""
    total = 0.0
    for s in prompt_lens:
        total += cfg["num_hidden_layers"] * (2 * active_layer_params(cfg) * s
                                             + attention_flops(cfg, s))
        total += 2 * head_params(cfg) * head_positions
    return total


# ----------------------------------------------------------------- training

def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3 x forward) per token of a ``seq``-long causal
    sequence, every position through the head; recomputation not counted."""
    fwd = (cfg["num_hidden_layers"] * (2 * active_layer_params(cfg)
                                       + attention_flops(cfg, seq) / seq)
           + 2 * head_params(cfg))
    return 3 * fwd
