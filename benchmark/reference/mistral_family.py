"""Plain reference of the Mistral family: Mistral-7B (dense) and Mixtral (MoE).

Decoder layer, from the published modelling code (transformers
``modeling_mistral.py`` / ``modeling_mixtral.py``):

    h = x + Attn(RMSNorm(x))          GQA, rotary on q and k, causal
    y = h + FFN(RMSNorm(h))           dense:  down(silu(gate(z)) * up(z))
                                      Mixtral: sum over the top-2 experts of
                                      softmax(router(z)), renormalised over
                                      the chosen two, of that expert's FFN

float32 throughout, ``default_matmul_precision("highest")`` (a TPU otherwise
multiplies float32 in bf16 passes). It walks the PROGRAM'S parameter tree -
``model.layers.block`` leaves stacked over layers - one layer, and one expert,
at a time, so only that slice is ever held in float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import F32, causal_attention, f32, rotary


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _attention(x, w, theta):
    q = jnp.einsum("bsh,hnd->bsnd", x, w["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", x, w["k_kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", x, w["v_kernel"])
    d = q.shape[-1]
    o = causal_attention(rotary(q, theta, d), rotary(k, theta, d), v)
    return o.reshape(*o.shape[:2], -1)


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


@partial(jax.jit, static_argnames=("theta", "eps"))
def _attend(x, blk, theta, eps):
    """(h, z): the residual after attention, and its norm for the FFN."""
    blk = f32(blk)
    h = x + _attention(rms_norm(x, blk["input_norm"]["scale"], eps),
                       blk["attention"]["qkv"], theta) @ blk["attention"]["o_proj"]["kernel"]
    return h, rms_norm(h, blk["post_attn_norm"]["scale"], eps)


@partial(jax.jit, static_argnames=("top_k",))
def _route(z, router, top_k):
    """(tokens, experts) weights: softmax over ALL experts, the top-k kept
    and renormalised to sum to one."""
    probs = jax.nn.softmax(z @ f32(router), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(topi, probs.shape[-1], dtype=F32), axis=-2)
    return probs * chosen / jnp.sum(topv, axis=-1, keepdims=True)


@jax.jit
def _expert_add(acc, z, weight, gate, up, down):
    return acc + weight[..., None] * _swiglu(z, f32(gate), f32(up), f32(down))


@jax.jit
def _dense_ffn(h, z, mlp):
    mlp = f32(mlp)
    return h + _swiglu(z, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                       mlp["down_proj"]["kernel"])


@partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, w, eps):
    return rms_norm(x, jnp.asarray(scale, F32), eps) @ f32(w)


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        block = model["layers"]["block"]
        x = jnp.asarray(model["embed"]["embedding"], F32)[ids]
        layers = block["input_norm"]["scale"].shape[0]
        for l in range(layers):
            light = {k: jax.tree.map(lambda a: a[l], block[k])
                     for k in ("input_norm", "attention", "post_attn_norm")}
            h, z = _attend(x, light, theta, eps)
            if "moe" in block:
                moe = block["moe"]
                combine = _route(z, moe["router"]["kernel"][l], int(sizes["num_experts_per_tok"]))
                x = h
                for e in range(combine.shape[-1]):
                    x = _expert_add(x, z, combine[..., e], moe["experts"]["gate"][l, e],
                                    moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
            else:
                x = _dense_ffn(h, z, jax.tree.map(lambda a: a[l], block["mlp"]))
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        return _head(x, model["final_norm"]["scale"], params["lm_head"]["kernel"], eps)
