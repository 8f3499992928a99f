"""Operations and bytes a Mamba-2 / attention hybrid NEEDS, from its published
sizes (``configs/granite-4.0-h-micro.json``'s key names).

Needed, not executed, as ``opcount.py`` counts: a live step reads every weight
once, reads and writes the state of its LIVE rows only (the program moves every
slot's), and a prompt's recurrence is counted in its cheapest form, one token
at a time, over its REAL tokens (the program runs the chunked form over the
padded bucket). Each is a lower bound on what the program moves or multiplies,
so a share of a peak built on it cannot pass 100 %.

One multiply-add is two operations. Weights, K/V and the convolution's tail at
``bytes_per_el`` (2: bf16); the recurrent state in float32 (4), as the
configuration's ``assumed`` says.
"""

from __future__ import annotations

from typing import Iterable

STATE_BYTES_PER_EL = 4


def hybrid_config(record: dict):
    """A record's configuration if it has Mamba layers, else None (what the
    ``ssm.*`` readers ask first: silent on every other configuration's record)."""
    cfg = record.get("config") or {}
    return cfg if "mamba" in cfg.get("layer_types", ()) else None


def kinds(cfg: dict) -> tuple:
    """(Mamba layers, attention layers)."""
    types = cfg["layer_types"]
    return sum(t == "mamba" for t in types), sum(t == "attention" for t in types)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 mixer: in_proj, the depthwise conv and its bias, dt_bias,
    A_log and D a head, the gate norm, out_proj."""
    h, inner = cfg["hidden_size"], d_inner(cfg)
    in_proj = h * (2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
                   + cfg["mamba_n_heads"])
    conv = conv_dim(cfg) * cfg["mamba_d_conv"] + (conv_dim(cfg) if cfg["mamba_conv_bias"] else 0)
    return in_proj + conv + 3 * cfg["mamba_n_heads"] + inner + inner * h


def attention_params(cfg: dict) -> int:
    n, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = cfg["hidden_size"]
    return h * n * d + 2 * h * nkv * d + n * d * h


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def layer_params(cfg: dict, kind: str) -> int:
    """A whole layer of ``kind``: its mixer, its MLP and its two norms."""
    mixer = mamba_params(cfg) if kind == "mamba" else attention_params(cfg)
    return mixer + mlp_params(cfg) + 2 * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter: the layers, the final norm, the embedding (tied: once)."""
    mamba, attention = kinds(cfg)
    tied = 1 if cfg.get("tie_word_embeddings", True) else 2
    return (mamba * layer_params(cfg, "mamba") + attention * layer_params(cfg, "attention")
            + cfg["hidden_size"] + tied * head_params(cfg))


def state_elements(cfg: dict) -> int:
    """Numbers of one Mamba layer's recurrent state, one slot."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def state_bytes_per_slot(cfg: dict, bytes_per_el: int = 2) -> int:
    """One slot's state in every Mamba layer: the recurrent state in float32
    and the last ``d_conv - 1`` inputs of the convolution."""
    mamba, _ = kinds(cfg)
    tail = (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * bytes_per_el
    return mamba * (state_elements(cfg) * STATE_BYTES_PER_EL + tail)


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """K and V of one token in the attention layers only."""
    _, attention = kinds(cfg)
    return attention * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el


# ------------------------------------------------------------------- decode

def decode_step_bytes(cfg: dict, rows: float, context_tokens: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes a step of ``rows`` live rows must move: every weight once (the
    tied table is the head), those rows' state read AND written, and the
    cached keys and values of ``context_tokens`` tokens (summed over the rows)."""
    return (total_params(cfg) * bytes_per_el
            + rows * 2 * state_bytes_per_slot(cfg, bytes_per_el)
            + context_tokens * kv_bytes_per_token(cfg, bytes_per_el))


def recurrence_flops_per_token(cfg: dict) -> float:
    """One token through one Mamba layer's recurrence, one token at a time:
    decay the state, add dt x (x) B (three operations an element), read y =
    S C (two)."""
    return 5 * state_elements(cfg)


def decode_step_flops(cfg: dict, rows: float, context_tokens: float) -> float:
    """Every weight multiplies each live row's one token (the embedding as the
    head), the recurrence once a Mamba layer, q.k and p.v over the cached tokens."""
    mamba, attention = kinds(cfg)
    per_row = 2 * total_params(cfg) + mamba * recurrence_flops_per_token(cfg)
    cached = attention * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * context_tokens
    return rows * per_row + cached


def decode_step_roofline_s(cfg: dict, rows: float, context_tokens: float, peaks: dict) -> float:
    """The least time the whole live step can take on this chip: the larger of
    its bytes over the HBM's rate and its operations over the bf16 peak."""
    return max(decode_step_bytes(cfg, rows, context_tokens) / peaks["hbm_bytes_per_s"],
               decode_step_flops(cfg, rows, context_tokens) / peaks["bf16_flops_per_s"])


# ------------------------------------------------------------------ prefill

def insert_flops(cfg: dict, prompt_lens: Iterable[int]) -> float:
    """FLOPs to prefill these prompts' REAL tokens and give one row of logits
    each: every layer's weights a token, the recurrence a token and Mamba
    layer, causal attention over s (s + 1) / 2 pairs in the attention layers,
    the head once a prompt."""
    mamba, attention = kinds(cfg)
    layers = (mamba * layer_params(cfg, "mamba") + attention * layer_params(cfg, "attention"))
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    total = 0.0
    for s in prompt_lens:
        total += s * (2 * layers + mamba * recurrence_flops_per_token(cfg))
        total += attention * 2 * 2 * n * d * s * (s + 1) / 2
        total += 2 * head_params(cfg)
    return total
