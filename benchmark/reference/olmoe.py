"""Plain reference of OLMoE (``model_type: olmoe``; OLMoE-1B-7B).

Decoder layer, from the published modelling code (transformers
``modeling_olmoe.py``):

    q = RMSNorm_q(x Wq)        over the WHOLE projection (all heads together,
    k = RMSNorm_k(x Wk)        num_heads x head_dim wide), before the split
    v = x Wv                   into heads
    rotary (rotate_half, every head dim) on q and k;
    causal softmax(q k^T / sqrt(head_dim)) v;  o_proj
    h = x + Attn(RMSNorm(x))
    y = h + MoE(RMSNorm(h))
    MoE(z) = sum over the top-k experts e of p_e * down_e(silu(gate_e z) * up_e z)
             p = softmax(z W_r) over ALL experts; the k kept probabilities are
             used AS THEY ARE (``norm_topk_prob: false``: they sum to less
             than one) unless ``sizes["norm_topk_prob"]`` is true

    logits = RMSNorm(y_last) W_head      (untied head, no biases, clip_qkv null)

float32 throughout, ``default_matmul_precision("highest")`` (a TPU otherwise
multiplies float32 in bf16 passes). Departures from the published code: none
in the mathematics. The layout is the PROGRAM'S parameter tree
(``model.layers.block`` leaves stacked over layers; q/k/v kernels shaped
(hidden, heads, head_dim); expert weights (layers, experts, in, out)), walked
one layer and one expert at a time, so only that slice is ever held in
float32; an expert no token chose adds exactly zero, as in the published loop
that skips it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import F32, causal_attention, f32, rotary


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


@partial(jax.jit, static_argnames=("theta", "eps"))
def _attend(x, blk, theta, eps):
    """(h, z): the residual after attention, and its norm for the experts."""
    blk = f32(blk)
    att = blk["attention"]
    a = rms_norm(x, blk["input_norm"]["scale"], eps)
    heads = att["qkv"]["q_kernel"].shape[1:]                       # (n, d)
    kv_heads = att["qkv"]["k_kernel"].shape[1:]
    flat = lambda w: w.reshape(w.shape[0], -1)                      # noqa: E731
    q = rms_norm(a @ flat(att["qkv"]["q_kernel"]), att["q_norm"], eps)
    k = rms_norm(a @ flat(att["qkv"]["k_kernel"]), att["k_norm"], eps)
    v = a @ flat(att["qkv"]["v_kernel"])
    q = q.reshape(*q.shape[:2], *heads)
    k = k.reshape(*k.shape[:2], *kv_heads)
    v = v.reshape(*v.shape[:2], *kv_heads)
    d = heads[1]
    o = causal_attention(rotary(q, theta, d), rotary(k, theta, d), v)
    h = x + o.reshape(*o.shape[:2], -1) @ att["o_proj"]["kernel"]
    return h, rms_norm(h, blk["post_attn_norm"]["scale"], eps)


@partial(jax.jit, static_argnames=("top_k", "renormalise"))
def _route(z, router, top_k, renormalise):
    """(tokens, experts) weights: softmax over ALL experts in float32; the
    top-k kept as they are, or renormalised to sum to one."""
    probs = jax.nn.softmax(z @ f32(router), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    kept = probs * jnp.sum(jax.nn.one_hot(topi, probs.shape[-1], dtype=F32), axis=-2)
    return kept / jnp.sum(topv, axis=-1, keepdims=True) if renormalise else kept


@jax.jit
def _expert_add(acc, z, weight, gate, up, down):
    gate, up, down = f32(gate), f32(up), f32(down)
    return acc + weight[..., None] * ((jax.nn.silu(z @ gate) * (z @ up)) @ down)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, w, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps) @ f32(w)


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    top_k = int(sizes["num_experts_per_tok"])
    renormalise = bool(sizes.get("norm_topk_prob", False))
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        block = model["layers"]["block"]
        moe = block["moe"]
        x = jnp.asarray(model["embed"]["embedding"], F32)[ids]
        for l in range(block["input_norm"]["scale"].shape[0]):
            light = {k: jax.tree.map(lambda a: a[l], block[k])
                     for k in ("input_norm", "attention", "post_attn_norm")}
            h, z = _attend(x, light, theta, eps)
            combine = _route(z, moe["router"]["kernel"][l], top_k, renormalise)
            x = h
            for e in range(combine.shape[-1]):
                x = _expert_add(x, z, combine[..., e], moe["experts"]["gate"][l, e],
                                moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        return _head(x, model["final_norm"]["scale"], params["lm_head"]["kernel"], eps)
