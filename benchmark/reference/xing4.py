"""Plain reference of Xing4.0 (``model_type: xing4_0``,
XingChen-AGI/Xing4.0-29B-A4B).

The published ``config.json`` names the residual path by its keys (``hc_mult``
4, ``hc_sinkhorn_iters`` 20, ``hc_eps`` 1e-6, ``mhc_h_res_clamp_min/max``
-30 / 30): the manifold-constrained hyper-connections of arXiv:2512.24880 over
the hyper-connections of arXiv:2409.19606. ``n = hc_mult``, ``C`` the hidden
size; a token's state is ``X`` in ``R^{n x C}``; ``F`` is a sub-block WITH its
own input RMSNorm. For EACH sub-block (two a layer: the attention; the dense
MLP or the experts), each with its own ``phi, a, b``:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)       in R^{nC}, no learned gain
    H~_pre  = a_pre  * (x~ phi_pre)  + b_pre               phi_pre, phi_post in R^{nC x n}
    H~_post = a_post * (x~ phi_post) + b_post
    H~_res  = a_res  * mat(x~ phi_res) + b_res             phi_res in R^{nC x n^2}, mat: row-major
    H_pre   = sigmoid(H~_pre)         H_post = 2 sigmoid(H~_post)
    M_0     = exp(clip(H~_res, clamp_min, clamp_max))
    M_t     = T_r(T_c(M_{t-1})),  t = 1..hc_sinkhorn_iters
              T_c divides each column by (its sum + hc_eps), T_r each row likewise
    H_res   = M_20
    u       = sum_i H_pre[i] X[i]
    y       = F(u)
    X'[i]   = sum_j H_res[i, j] X[j] + H_post[i] y

    entry:  X_0[i] = embedding, every i          exit:  h = sum_i X_L[i]
    logits = RMSNorm(h) W_head                   (untied head)

The sub-blocks (all linears without bias, RMSNorm with ``rms_norm_eps``):

    latent attention (MLA), DeepSeek-V2's, expanded form (``deepseek_v2.py``
    beside this file has the equations) at this model's sizes; YaRN over the
    rope dims; softmax scale (nope + rope)^-1/2 * yarn_mscale(factor,
    mscale_all_dim)^2.
    the first ``first_k_dense_replace`` layers: F(u) = down(silu(gate z) * up z),
      z = RMSNorm(u)
    the rest (``noaux_tc``, ``n_group`` 1: no groups): s = sigmoid(z W_r) over
      the routed experts, float32; c = s + e_score_correction_bias; the
      ``num_experts_per_tok`` largest c are chosen (a tie to the lower expert);
      a chosen expert weighs its s (not c) over the sum of the chosen s
      (``norm_topk_prob``), times ``routed_scaling_factor``;
      F(u) = sum_i w_i SwiGLU_i(z) + SwiGLU_shared(z)

float32 throughout, ``default_matmul_precision("highest")``, no cache, no
kernel, no batching of the mix: its own Sinkhorn loop over ``(.., n, n)``
matrices (sums over an axis, a true division), its own route by a stable sort.
The parameter tree is the PROGRAM's: a mix's ``phi`` is kept there as
``(n (n + 2), n, C)``, outputs first: row ``k`` is column ``k`` of ``[phi_pre |
phi_post | phi_res]`` above; ``alpha`` is ``(a_pre, a_post, a_res)``, ``beta``
``[b_pre | b_post | vec(b_res)]``.

Where the published config is silent, this file's reading (the program takes
the same one; ``configs/xing4.0-29b-a4b.json::assumed`` lists them):
* ``x~``'s norm has NO learned gain (a gain would fold into ``phi``'s rows);
  ``hc_eps`` is added to the mean square inside the root, and to each column's
  and row's sum in the Sinkhorn;
* columns are normalised before rows in a Sinkhorn turn, and every one of the
  20 turns is taken (no early stop);
* the clip is applied to ``H~_res`` before the ``exp`` (that is what bounds of
  -30 / 30 can mean: ``exp(30)`` is finite in float32);
* the streams are replicated in and summed out;
* coefficients are float32 (here everything is).

Departures from the published description:
* rotary pairs (i, i + d/2) as everywhere in this repository (the published
  code de-interleaves first: a fixed column permutation under seeded weights).
* ``num_nextn_predict_layers``: the multi-token-prediction module is a
  training objective and an optional draft head; it is not part of the forward
  pass that yields a token's logits and is not built.

Memory: the program's parameter tree walked one layer and one expert at a time;
attention one row and ``HEAD_BLOCK`` heads at a time; the head in vocabulary
blocks; the streams of the probe's rows are 0.12 GB in float32.
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
    _swiglu,
    rms_norm,
    rope_tables,
    rotate,
)

HEAD_BLOCK = 16


# ------------------------------------------------------------ the stream mix

def sinkhorn(m, iters: int, eps: float):
    """``m`` (..., n, n) positive: ``iters`` times columns, then rows."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)      # a column's sum runs over rows
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


@partial(jax.jit, static_argnames=("iters", "eps", "lo", "hi"))
def mix_coeff(x, mix, iters, eps, lo, hi):
    """``(H_pre (.., n), H_post (.., n), H_res (.., n, n))`` of ``x`` (.., n, C)."""
    n, width = x.shape[-2:]
    flat = x.reshape(*x.shape[:-2], n * width)
    unit = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    phi = f32(mix["phi"]).reshape(n * (n + 2), n * width).T          # (nC, n (n + 2))
    a, b = f32(mix["alpha"]), f32(mix["beta"])
    proj = unit @ phi
    pre = a[0] * proj[..., :n] + b[:n]
    post = a[1] * proj[..., n: 2 * n] + b[n: 2 * n]
    res = (a[2] * proj[..., 2 * n:] + b[2 * n:]).reshape(*proj.shape[:-1], n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(jnp.exp(jnp.clip(res, lo, hi)), iters, eps))


@jax.jit
def mix_read(x, pre):
    return jnp.einsum("...n,...nc->...c", pre, x)


@jax.jit
def mix_write(x, y, post, res):
    return jnp.einsum("...ij,...jc->...ic", res, x) + post[..., None] * y[..., None, :]


def enter_streams(h, n: int):
    return jnp.repeat(h[..., None, :], n, axis=-2)


def exit_streams(x):
    return jnp.sum(x, axis=-2)


def mixed(x, mix, sizes, sub_block):
    """One sub-block under its mix: ``x`` (b, s, n, C) -> (b, s, n, C)."""
    pre, post, res = mix_coeff(
        x, mix, int(sizes["hc_sinkhorn_iters"]), float(sizes["hc_eps"]),
        float(sizes["mhc_h_res_clamp_min"]), float(sizes["mhc_h_res_clamp_max"]))
    return mix_write(x, sub_block(mix_read(x, pre)), post, res)


# ------------------------------------------------------------ the sub-blocks

@partial(jax.jit, static_argnames=("eps", "scale", "nope", "v_dim"))
def attention(u, blk, cos, sin, eps, scale, nope, v_dim):
    """Latent attention of the normed ``u`` (b, s, C), expanded form; no
    residual: what the mix writes back."""
    blk = f32(blk)
    att = blk["attention"]
    a = rms_norm(u, blk["input_norm"]["scale"], eps)
    c_q = rms_norm(a @ att["q_a_proj"], att["q_a_norm"]["scale"], eps)
    down = a @ att["kv_a_proj"]
    rank = att["kv_a_norm"]["scale"].shape[0]
    c_kv = rms_norm(down[..., :rank], att["kv_a_norm"]["scale"], eps)
    w_uq = att["q_b_proj"]
    w_ukv = jnp.concatenate([att["k_b_proj"], att["v_b_proj"]], axis=-1)
    n = w_uq.shape[1]
    s = u.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_row(row):
        c_q1, c_kv1, k_r1 = row
        k_rope = rotate(k_r1, cos, sin)

        def heads(w):
            w_q, w_kv = w
            q = jnp.einsum("sr,rnd->snd", c_q1, w_q)
            kv = jnp.einsum("sr,rnd->snd", c_kv1, w_kv)
            q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            score = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
                     + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) * scale
            p = jax.nn.softmax(jnp.where(causal[None], score, -jnp.inf), axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, v)

        block = min(HEAD_BLOCK, n)
        split = lambda w: w.reshape(w.shape[0], n // block, block, w.shape[2]).transpose(1, 0, 2, 3)  # noqa: E731
        o = jax.lax.map(heads, (split(w_uq), split(w_ukv)))
        return o.transpose(1, 0, 2, 3).reshape(s, n * v_dim)

    o = jax.lax.map(one_row, (c_q, c_kv, down[..., rank:]))
    return o @ att["o_proj"]["kernel"]


@partial(jax.jit, static_argnames=("eps",))
def normed(u, scale, eps):
    return rms_norm(u, f32(scale), eps)


@jax.jit
def mlp(z, weights):
    weights = f32(weights)
    return _swiglu(z, weights["gate_proj"]["kernel"], weights["up_proj"]["kernel"],
                   weights["down_proj"]["kernel"])


def route(z, router, bias, top_k: int, renormalise: bool, scale: float):
    """(tokens.., experts) weights of the ``noaux_tc`` route without groups."""
    s = jax.nn.sigmoid(z @ f32(router))
    c = s + f32(bias)
    chosen = jnp.sum(jax.nn.one_hot(jnp.argsort(-c, axis=-1, stable=True)[..., :top_k],
                                    s.shape[-1], dtype=F32), axis=-2)
    weights = s * chosen
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scale


def experts(z, block, l, sizes):
    """The routed experts' weighted sum plus the shared expert, of the normed
    ``z``; ``block`` the stacked expert layers, ``l`` the layer."""
    moe = block["moe"]
    combine = route(z, moe["router"]["kernel"][l], moe["router"]["e_score_correction_bias"][l],
                    int(sizes["num_experts_per_tok"]), bool(sizes["norm_topk_prob"]),
                    float(sizes["routed_scaling_factor"]))
    out = mlp(z, jax.tree.map(lambda a: a[l], block["shared_expert"]))
    for e in range(moe["experts"]["gate"].shape[1]):
        out = _expert_add(out, z, combine[..., e], moe["experts"]["gate"][l, e],
                          moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
    return out


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps = float(sizes["rms_norm_eps"])
    nope, v_dim = int(sizes["qk_nope_head_dim"]), int(sizes["v_head_dim"])
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        cos, sin, scale = rope_tables(ids.shape[1], sizes)
        x = enter_streams(f32(model["embed"]["embedding"][ids]), int(sizes["hc_mult"]))
        light = ("input_norm", "attention")
        stacks = [model[name]["block"] for name in ("dense_layers", "layers") if name in model]
        for block in stacks:
            for l in range(block["input_norm"]["scale"].shape[0]):
                at = {k: jax.tree.map(lambda a: a[l], block[k]) for k in light}
                x = mixed(x, jax.tree.map(lambda a: a[l], block["attn_mix"]), sizes,
                          lambda u: attention(u, at, cos, sin, eps, scale, nope, v_dim))

                def ffn(u):
                    z = normed(u, block["post_attn_norm"]["scale"][l], eps)
                    if "moe" not in block:
                        return mlp(z, jax.tree.map(lambda a: a[l], block["mlp"]))
                    return experts(z, block, l, sizes)

                x = mixed(x, jax.tree.map(lambda a: a[l], block["ffn_mix"]), sizes, ffn)
        h = exit_streams(x)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        h = _final_norm(h, model["final_norm"]["scale"], eps)
        w = params["lm_head"]["kernel"]
        return jnp.concatenate([_head_block(h, w[:, i: i + VOCAB_BLOCK])
                                for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)
