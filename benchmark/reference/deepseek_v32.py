"""Plain reference of DeepSeek-V3.2 (``model_type: deepseek_v32``).

Decoder layer, as the DeepSeek-V3.2 report and the published inference code
describe it; all linears without bias, RMSNorm with ``rms_norm_eps``; ``a`` the
layer's normed input, one row of ``s`` tokens at a time:

    latent attention (MLA), DeepSeek-V2's, expanded form (``deepseek_v2.py``
    beside this file has the equations): c_q = RMSNorm(a W_dq), heads of
    [q_nope | q_rope]; [c_kv | k_r] = a W_dkv, c_kv = RMSNorm(c_kv), ONE rotary
    key a token; [k_nope | v] per head = c_kv W_ukv; YaRN over the rope dims;
    softmax scale (nope + rope)^-1/2 * yarn_mscale(factor, mscale_all_dim)^2.

    the lightning indexer, every layer its own:
      q^I_{t,j} = (c_q_t W^I_q)_j     j = 1..index_n_heads, index_head_dim wide
      k^I_s     = LayerNorm(a_s W^I_k) (scale and bias, eps = rms_norm_eps),
                  ONE key a token, shared by the index heads
      both rotated on their FIRST qk_rope_head_dim dims by the layer's tables
      w_{t,j}   = (a_t W^I_w)_j * index_n_heads^-1/2 * index_head_dim^-1/2
      I_{t,s}   = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)               s <= t
      S_t       = the index_topk largest I_{t,s} over s <= t, all of them while
                  t < index_topk; a tie goes to the lower position
    softmax of token t over the tokens of S_t ONLY (the others masked out)

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
      the first ``first_k_dense_replace`` layers: down(silu(gate z) * up z)
      the rest (``noaux_tc``): p = sigmoid(z W_g) over ALL routed experts,
      float32; c = p + e_score_correction_bias; a group (``n_group`` groups of
      consecutive experts) scores the sum of its two largest c; the
      ``topk_group`` best groups stay; the ``num_experts_per_tok`` largest c
      inside them are chosen; a chosen expert weighs its p (not c) over the sum
      of the chosen p, times ``routed_scaling_factor``;
      FFN(z) = sum_i w_i SwiGLU_i(z) + SwiGLU_shared(z)
    logits = RMSNorm(y_last) W_head      (untied head)

The chip's share of the experts is DeepSeek-V2's: the router is as wide as
published, the parameter tree holds experts ``experts_held_first .. + held``,
the weights are renormalised over ALL the chosen and what the absent experts
would have added is left out, here as in the program.

float32 throughout, ``default_matmul_precision("highest")``, no cache, no
kernel; the choice by a stable sort of its own, the route by sorts of its own.

Departures from the published description:
* the published indexer runs in FP8 after a Hadamard rotation of ``q^I`` and
  ``k^I``; the rotation is orthogonal, so the dot products are the same in
  exact arithmetic, and is left out; no FP8.
* rotary pairs (i, i + d/2) as everywhere in this repository (the published
  code de-interleaves first: a fixed column permutation under seeded weights).
* ``num_nextn_predict_layers``: the multi-token-prediction module is a
  training objective and an optional draft head; it is not part of the forward
  pass that yields a token's logits and is not built.

Memory: the program's parameter tree walked one layer and one expert at a time;
attention one row and ``HEAD_BLOCK`` heads at a time (scores of 4100 tokens and
8 heads are 0.54 GB), index scores one index head at a time into ONE (s, s)
array, the head in vocabulary blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import F32, f32
from .deepseek_v2 import (
    VOCAB_BLOCK,
    _expert_add,
    _final_norm,
    _head_block,
    _mlp_add,
    rms_norm,
    rope_tables,
    rotate,
)

HEAD_BLOCK = 8


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotate_first(x, cos, sin):
    """Rotary on the first ``2 x cos.shape[-1]`` dims of ``x`` (s, ..., d)."""
    rot = 2 * cos.shape[-1]
    return jnp.concatenate([rotate(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def index_scores(q, k, w):
    """``I`` (s, s): ``q`` (s, heads, d), ``k`` (s, d), ``w`` (s, heads); one
    index head at a time."""
    def add(total, head):
        q_j, w_j = head
        return total + w_j[:, None] * jax.nn.relu(q_j @ k.T), None

    s = q.shape[0]
    total, _ = jax.lax.scan(add, jnp.zeros((s, s), F32),
                            (q.transpose(1, 0, 2), w.transpose(1, 0)))
    return total


def chosen_mask(scores, top_k):
    """(s, s) bool: key s' is read by query t iff s' <= t and I[t, s'] is among
    the ``top_k`` largest of I[t, :t + 1]; ties to the lower position."""
    s = scores.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)          # place of each key in its query's order
    return causal & (rank < top_k)


@partial(jax.jit, static_argnames=("eps", "scale", "nope", "v_dim", "top_k"))
def _attend(x, blk, cos, sin, eps, scale, nope, v_dim, top_k):
    """(h, z): the residual after sparse latent attention, and its norm."""
    blk = f32(blk)
    att = blk["attention"]
    a = rms_norm(x, blk["input_norm"]["scale"], eps)
    c_q = rms_norm(a @ att["q_a_proj"], att["q_a_norm"]["scale"], eps)
    down = a @ att["kv_a_proj"]
    rank = att["kv_a_norm"]["scale"].shape[0]
    c_kv = rms_norm(down[..., :rank], att["kv_a_norm"]["scale"], eps)
    w_uq = att["q_b_proj"]
    w_ukv = jnp.concatenate([att["k_b_proj"], att["v_b_proj"]], axis=-1)
    n = w_uq.shape[1]
    s = x.shape[1]
    n_idx, d_idx = att["index_q_proj"].shape[1:]
    q_i = jnp.einsum("bsr,rjd->bsjd", c_q, att["index_q_proj"])
    k_i = layer_norm(a @ att["index_k_proj"], att["index_k_norm"]["scale"],
                     att["index_k_norm"]["bias"], eps)
    w_i = (a @ att["index_weights_proj"]) * (n_idx ** -0.5 * d_idx ** -0.5)

    def one_row(row):
        c_q1, c_kv1, k_r1, q_i1, k_i1, w_i1 = row
        k_rope = rotate(k_r1, cos, sin)
        read = chosen_mask(index_scores(rotate_first(q_i1, cos, sin),
                                        rotate_first(k_i1, cos, sin), w_i1), top_k)

        def heads(w):
            w_q, w_kv = w
            q = jnp.einsum("sr,rnd->snd", c_q1, w_q)
            kv = jnp.einsum("sr,rnd->snd", c_kv1, w_kv)
            q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            score = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
                     + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) * scale
            p = jax.nn.softmax(jnp.where(read[None], score, -jnp.inf), axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v)

        block = min(HEAD_BLOCK, n)
        split = lambda w: w.reshape(w.shape[0], n // block, block, w.shape[2]).transpose(1, 0, 2, 3)  # noqa: E731
        o = jax.lax.map(heads, (split(w_uq), split(w_ukv)))
        return o.transpose(1, 0, 2, 3).reshape(s, n * v_dim)

    o = jax.lax.map(one_row, (c_q, c_kv, down[..., rank:], q_i, k_i, w_i))
    h = x + o @ att["o_proj"]["kernel"]
    return h, rms_norm(h, blk["post_attn_norm"]["scale"], eps)


def _largest(x, k):
    """Indices of the ``k`` largest of the last axis, a tie to the lower."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


@partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group", "renormalise", "scale"))
def route(z, router, bias, top_k, n_group, topk_group, renormalise, scale):
    """(tokens, ALL routed experts) weights of the ``noaux_tc`` route."""
    p = jax.nn.sigmoid(z @ f32(router))
    c = p + f32(bias)
    e = p.shape[-1]
    grouped = c.reshape(*c.shape[:-1], n_group, e // n_group)
    group_score = jnp.sum(-jnp.sort(-grouped, axis=-1)[..., :2], axis=-1)
    kept = jnp.sum(jax.nn.one_hot(_largest(group_score, topk_group), n_group, dtype=F32), axis=-2)
    inside = jnp.where(jnp.repeat(kept, e // n_group, axis=-1) > 0, c, -jnp.inf)
    chosen = jnp.sum(jax.nn.one_hot(_largest(inside, top_k), e, dtype=F32), axis=-2)
    weights = p * chosen
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scale


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
                h, z = _attend(x, at, cos, sin, eps, scale, nope, v_dim,
                               int(sizes["index_topk"]))
                if "moe" not in block:
                    x = _mlp_add(h, z, jax.tree.map(lambda a: a[l], block["mlp"]))
                    continue
                moe = block["moe"]
                combine = route(z, moe["router"]["kernel"][l],
                                moe["router"]["e_score_correction_bias"][l],
                                int(sizes["num_experts_per_tok"]), int(sizes["n_group"]),
                                int(sizes["topk_group"]), bool(sizes["norm_topk_prob"]),
                                float(sizes["routed_scaling_factor"]))
                x = h
                if "shared_expert" in block:
                    x = _mlp_add(x, z, jax.tree.map(lambda a: a[l], block["shared_expert"]))
                for e in range(moe["experts"]["gate"].shape[1]):   # expert ``first + e``
                    x = _expert_add(x, z, combine[..., first + e], moe["experts"]["gate"][l, e],
                                    moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        x = _final_norm(x, model["final_norm"]["scale"], eps)
        w = params["lm_head"]["kernel"]
        return jnp.concatenate([_head_block(x, w[:, i: i + VOCAB_BLOCK])
                                for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)
