"""Plain reference of DeepSeek-V2 (``model_type: deepseek_v2``).

Decoder layer, from the published modelling code (transformers
``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2), all linears without
bias, RMSNorm with ``rms_norm_eps``:

    latent attention (MLA), every layer, PREFILL form (K and V expanded):
      c_q = RMSNorm(x W_dq);  q = c_q W_uq -> heads of [q_nope | q_rope]
      [c_kv | k_r] = x W_dkv;  c_kv = RMSNorm(c_kv);  k_rope = RoPE(k_r), ONE
      per token, shared by all heads;  q_rope = RoPE(q_rope)
      [k_nope | v] per head = c_kv W_ukv
      score[h] = (q_nope[h] . k_nope[h] + q_rope[h] . k_rope) * s
      s = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
      causal softmax in float32;  o = concat_h(P[h] v[h]) W_o
      RoPE is YaRN over the rope dims (``DeepseekV2YarnRotaryEmbedding``): a
      per-dimension blend of theta^(-2i/d) and the same over ``factor``, with
      a linear ramp between the dims whose wavelength makes ``beta_fast`` and
      ``beta_slow`` turns in ``original_max_position_embeddings`` positions;
      cos/sin scaled by yarn_mscale(factor, mscale) /
      yarn_mscale(factor, mscale_all_dim).
    h = x + Attn(RMSNorm(x))
    y = h + FFN(RMSNorm(h))
      the first ``first_k_dense_replace`` layers: down(silu(gate z) * up z)
      the rest: p = softmax(z W_g) over ALL routed experts, float32; a group's
      score is the largest p among its experts (``n_group`` groups of
      consecutive experts); the ``topk_group`` best groups are kept, the rest
      masked to 0; top-k of what is left; the chosen p are used AS THEY ARE
      (``norm_topk_prob: false``) times ``routed_scaling_factor``;
      FFN(z) = sum_i w_i SwiGLU_i(z) + SwiGLU_shared(z)
    logits = RMSNorm(y_last) W_head      (untied head)

The chip's share of the experts: the router is as wide as published
(``sizes["router_experts"]``) and chooses among all of them, the parameter
tree holds experts ``experts_held_first .. + held`` only, and what the absent
experts would have added is left out, here as in the program
(``/opt/skills/guides/model-configs`` section 4). With all of them held this
is the uncut model.

float32 throughout, ``default_matmul_precision("highest")``. The absorbed
decode form (``q_nope W_uk^T`` against the cached latent) is the PROGRAM's;
this file never takes it, so that the comparison is what shows the two agree.

Departure from the published code: the rotary convention. HF de-interleaves
the rope dims (pairs (2i, 2i+1)) before its ``rotate_half``; this file and the
program rotate pairs (i, i + d/2) as ``common.rotary`` and the program's
``apply_rotary`` do. With seeded weights the two differ by a fixed permutation
of the rope columns of ``W_uq`` and ``W_dkv``; a converter of published
weights has to apply it. Nothing else departs.

Memory: the layout is the PROGRAM's parameter tree (``model.dense_layers`` and
``model.layers``, ``block`` leaves stacked over layers), walked one layer and
one expert at a time; attention runs one row and one block of heads at a time
(scores of one row of 2052 tokens and 16 heads are 0.27 GB); the embedding is
gathered before it is widened and the head is multiplied in vocabulary blocks.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, f32

HEAD_BLOCK = 16
VOCAB_BLOCK = 25600


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# --------------------------------------------------------------------- YaRN

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """(dim / 2,) rotary frequencies of ``DeepseekV2YarnRotaryEmbedding``."""
    factor, orig = float(scaling["factor"]), int(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / ((high if high != low else high + 0.001) - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                       # 1: the dim keeps its own frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_tables(seq: int, sizes: dict):
    """cos, sin (seq, rope / 2) in float32, and the softmax scale."""
    rope = int(sizes["qk_rope_head_dim"])
    scaling = sizes.get("rope_scaling")
    theta = float(sizes["rope_theta"])
    scale = (int(sizes["qk_nope_head_dim"]) + rope) ** -0.5
    if scaling:
        inv = yarn_inv_freq(rope, theta, scaling)
        amp = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
               / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
        if scaling.get("mscale_all_dim"):
            scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    else:
        inv = (1.0 / theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope)).astype(np.float32)
        amp = 1.0
    ang = np.arange(seq, dtype=np.float32)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang) * amp, F32), jnp.asarray(np.sin(ang) * amp, F32), float(scale)


def rotate(x, cos, sin):
    """Pairs (i, i + d/2) of the last axis of ``x`` (s, ..., d); cos/sin (s, d/2)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------- attention

@partial(jax.jit, static_argnames=("eps", "scale", "nope", "v_dim"))
def _attend(x, blk, cos, sin, eps, scale, nope, v_dim):
    """(h, z): the residual after latent attention, and its norm for the FFN."""
    blk = f32(blk)
    att = blk["attention"]
    a = rms_norm(x, blk["input_norm"]["scale"], eps)
    c_q = rms_norm(a @ att["q_a_proj"], att["q_a_norm"]["scale"], eps)              # (b, s, q_rank)
    down = a @ att["kv_a_proj"]                                            # (b, s, rank + rope)
    rank = att["kv_a_norm"]["scale"].shape[0]
    c_kv = rms_norm(down[..., :rank], att["kv_a_norm"]["scale"], eps)
    # W_ukv, which the program keeps as its two halves (k_nope's, v's)
    w_uq = att["q_b_proj"]                                                 # (rank, n, d)
    w_ukv = jnp.concatenate([att["k_b_proj"], att["v_b_proj"]], axis=-1)
    n = w_uq.shape[1]
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_row(row):
        c_q1, c_kv1, k_r1 = row                                            # (s, ...)
        k_rope = rotate(k_r1, cos, sin)                                    # (s, rope), all heads'

        def heads(w):
            w_q, w_kv = w                                                  # (rank, HEAD_BLOCK, d)
            q = jnp.einsum("sr,rnd->snd", c_q1, w_q)
            kv = jnp.einsum("sr,rnd->snd", c_kv1, w_kv)
            q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            score = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
                     + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) * scale
            p = jax.nn.softmax(jnp.where(causal[None], score, -jnp.inf), axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v)                        # (s, HEAD_BLOCK, v)

        block = min(HEAD_BLOCK, n)
        split = lambda w: w.reshape(w.shape[0], n // block, block, w.shape[2]).transpose(1, 0, 2, 3)  # noqa: E731
        o = jax.lax.map(heads, (split(w_uq), split(w_ukv)))                # (n / block, s, block, v)
        return o.transpose(1, 0, 2, 3).reshape(s, n * v_dim)

    o = jax.lax.map(one_row, (c_q, c_kv, down[..., rank:]))
    h = x + o @ att["o_proj"]["kernel"]
    return h, rms_norm(h, blk["post_attn_norm"]["scale"], eps)


# ---------------------------------------------------------------------- FFN

def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


@jax.jit
def _mlp_add(h, z, mlp):
    mlp = f32(mlp)
    return h + _swiglu(z, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                       mlp["down_proj"]["kernel"])


@partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group", "renormalise", "scale"))
def route(z, router, top_k, n_group, topk_group, renormalise, scale):
    """(tokens, ALL routed experts) weights: softmax in float32, the best
    ``topk_group`` of ``n_group`` groups by their largest probability, top-k
    inside them, kept as they are (or renormalised), times ``scale``."""
    probs = jax.nn.softmax(z @ f32(router), axis=-1)
    e = probs.shape[-1]
    allowed = probs
    if n_group > 1:
        best = jnp.max(probs.reshape(*probs.shape[:-1], n_group, e // n_group), axis=-1)
        _, groups = jax.lax.top_k(best, topk_group)
        keep = jnp.sum(jax.nn.one_hot(groups, n_group, dtype=F32), axis=-2)
        allowed = probs * jnp.repeat(keep, e // n_group, axis=-1)
    topv, topi = jax.lax.top_k(allowed, top_k)
    kept = probs * jnp.sum(jax.nn.one_hot(topi, e, dtype=F32), axis=-2)
    if renormalise:
        kept = kept / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return kept * scale


@jax.jit
def _expert_add(acc, z, weight, gate, up, down):
    return acc + weight[..., None] * _swiglu(z, f32(gate), f32(up), f32(down))


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, eps):
    return rms_norm(x, f32(scale), eps)


@jax.jit
def _head_block(x, w):
    return x @ f32(w)


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps = float(sizes["rms_norm_eps"])
    nope, v_dim = int(sizes["qk_nope_head_dim"]), int(sizes["v_head_dim"])
    first = int(sizes.get("experts_held_first", 0))
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        cos, sin, scale = rope_tables(ids.shape[1], sizes)
        x = f32(model["embed"]["embedding"][ids])
        light = ("input_norm", "attention", "post_attn_norm")
        stacks = [model[name]["block"] for name in ("dense_layers", "layers") if name in model]
        for block in stacks:
            for l in range(block["input_norm"]["scale"].shape[0]):
                at = {k: jax.tree.map(lambda a: a[l], block[k]) for k in light}
                h, z = _attend(x, at, cos, sin, eps, scale, nope, v_dim)
                if "moe" not in block:
                    x = _mlp_add(h, z, jax.tree.map(lambda a: a[l], block["mlp"]))
                    continue
                moe = block["moe"]
                combine = route(z, moe["router"]["kernel"][l], int(sizes["num_experts_per_tok"]),
                                int(sizes.get("n_group", 1)), int(sizes.get("topk_group", 1)),
                                bool(sizes.get("norm_topk_prob", False)),
                                float(sizes.get("routed_scaling_factor", 1.0)))
                x = h
                if "shared_expert" in block:
                    x = _mlp_add(x, z, jax.tree.map(lambda a: a[l], block["shared_expert"]))
                held = moe["experts"]["gate"].shape[1]
                for e in range(held):          # expert ``first + e`` of the router's
                    x = _expert_add(x, z, combine[..., first + e], moe["experts"]["gate"][l, e],
                                    moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        x = _final_norm(x, model["final_norm"]["scale"], eps)
        w = params["lm_head"]["kernel"]
        return jnp.concatenate([_head_block(x, w[:, i: i + VOCAB_BLOCK])
                                for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)
