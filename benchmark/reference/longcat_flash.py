"""Plain reference of LongCat-Flash (``meituan-longcat/LongCat-Flash-Chat``).

One layer, from the published modelling code (``modeling_longcat_flash.py``)
and the technical report's shortcut-connected block; all linears without bias,
RMSNorm with ``rms_norm_eps``; ``A_i`` a latent attention, ``D_i`` a gated-SiLU
MLP ``down(silu(gate z) * up z)`` of ``ffn_hidden_size``, ``M`` the experts:

    h = x + A_0(norm_in0(x));   u = norm_post0(h);   m = M(u)
    h = h + D_0(u);   h = h + A_1(norm_in1(h));   y = h + D_1(norm_post1(h)) + m
    logits = RMSNorm(y_last) W_head      (untied head)

    A_i:  c_q = RMSNorm(x W_dq) * sqrt(hidden_size / q_lora_rank)
          q = c_q W_uq -> heads of [q_nope | q_rope]
          [c_kv | k_r] = x W_dkv;  c_kv = RMSNorm(c_kv) * sqrt(hidden_size / kv_lora_rank)
          k_rope = RoPE(k_r), ONE per token, shared by all heads, not scaled;
          q_rope = RoPE(q_rope);  [k_nope | v] per head = c_kv W_ukv
          score[h] = (q_nope[h] . k_nope[h] + q_rope[h] . k_rope) / sqrt(nope + rope)
          causal softmax in float32;  o = concat_h(P[h] v[h]) W_o
          (each scale only where ``mla_scale_q_lora`` / ``mla_scale_kv_lora``
          says so; plain rope at ``rope_theta``)
    M(u): s = softmax(u W_r) over ``router_experts + zero_expert_num``;
          the ``moe_topk`` largest of ``s + e_score_correction_bias``; a
          chosen e weighs ``routed_scaling_factor * s_e`` (not renormalised);
          M(u) = sum_{chosen e < router_experts} w_e SwiGLU_e(u)
                 + (sum_{chosen e >= router_experts} w_e) u
          (the last ``zero_expert_num`` experts are the identity)

The chip's share of the experts: the router is as wide as published and
chooses among all of them, the parameter tree holds real experts
``experts_held_first .. + held`` only, and what the absent experts would have
added is left out, here as in the program; the identity part belongs to the
chip that owns the token and is computed in full. With all of them held this
is the uncut model.

float32 throughout, ``default_matmul_precision("highest")``; only the expanded
form of the attention (the absorbed decode is the PROGRAM's).

Departures from the published description:

* the rotary convention: pairs (i, i + d/2) of the rope dims, as the program's
  ``apply_rotary`` rotates; the published code de-interleaves pairs
  (2i, 2i + 1) first. With seeded weights a fixed permutation of the rope
  columns of ``W_uq`` and ``W_dkv`` (``reference/deepseek_v2.py`` notes the
  same of its own);
* the two low-rank scales take the modelling code's form, ``(hidden_size /
  rank) ** 0.5``; the config file gives the two booleans only.

Memory: the layout is the PROGRAM's parameter tree (``model.layers.block``:
``sub_0``, ``sub_1`` and ``moe``, leaves stacked over layers), walked one layer, one sub-layer and one expert at
a time; attention runs one row and one block of heads at a time; the embedding
is gathered before it is widened and the head is multiplied in vocabulary
blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, f32

HEAD_BLOCK = 16
VOCAB_BLOCK = 32768


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_tables(seq: int, dim: int, theta: float):
    """cos, sin ``(seq, dim / 2)``, plain rope."""
    inv = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    ang = np.arange(seq, dtype=np.float32)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rotate(x, cos, sin):
    """Pairs (i, i + d/2) of the last axis of ``x`` (s, ..., d); cos/sin (s, d/2)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "nope", "v_dim", "q_scale", "kv_scale"))
def attention(x, norm_scale, att, cos, sin, eps, nope, v_dim, q_scale, kv_scale):
    """``A(norm(x))`` of one sub-layer, ``x`` (b, s, hidden)."""
    att = f32(att)
    a = rms_norm(x, f32(norm_scale), eps)
    c_q = rms_norm(a @ att["q_a_proj"], att["q_a_norm"]["scale"], eps) * q_scale
    down = a @ att["kv_a_proj"]                                            # (b, s, rank + rope)
    rank = att["kv_a_norm"]["scale"].shape[0]
    c_kv = rms_norm(down[..., :rank], att["kv_a_norm"]["scale"], eps) * kv_scale
    w_uq = att["q_b_proj"]                                                 # (q_rank, n, nope + rope)
    w_ukv = jnp.concatenate([att["k_b_proj"], att["v_b_proj"]], axis=-1)   # (rank, n, nope + v)
    n, s = w_uq.shape[1], x.shape[1]
    scale = (w_uq.shape[2]) ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_row(row):
        c_q1, c_kv1, k_r1 = row
        k_rope = rotate(k_r1, cos, sin)                                    # (s, rope), every head's

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
        o = jax.lax.map(heads, (split(w_uq), split(w_ukv)))                # (n / block, s, block, v)
        return o.transpose(1, 0, 2, 3).reshape(s, n * v_dim)

    o = jax.lax.map(one_row, (c_q, c_kv, down[..., rank:]))
    return o @ att["o_proj"]["kernel"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


@jax.jit
def mlp(z, tree):
    tree = f32(tree)
    return _swiglu(z, tree["gate_proj"]["kernel"], tree["up_proj"]["kernel"],
                   tree["down_proj"]["kernel"])


@partial(jax.jit, static_argnames=("top_k", "scale"))
def route(z, router, bias, top_k, scale):
    """(tokens, EVERY expert of the router) weights: softmax in float32, the
    ``top_k`` largest of ``softmax + bias``, the chosen softmax values as they
    are times ``scale``."""
    probs = jax.nn.softmax(z @ f32(router), axis=-1)
    _, topi = jax.lax.top_k(probs + f32(bias), top_k)
    return probs * jnp.sum(jax.nn.one_hot(topi, probs.shape[-1], dtype=F32), axis=-2) * scale


@jax.jit
def expert_add(acc, z, weight, gate, up, down):
    return acc + weight[..., None] * _swiglu(z, f32(gate), f32(up), f32(down))


def experts(u, moe, l, sizes):
    """``M(u)`` of layer ``l``: the held real experts' part and the identity
    experts' part."""
    real, first = int(sizes["router_experts"]), int(sizes.get("experts_held_first", 0))
    router = moe["router"]
    bias = (router["e_score_correction_bias"][l] if "e_score_correction_bias" in router
            else jnp.zeros((router["kernel"].shape[-1],), F32))
    combine = route(u, router["kernel"][l], bias, int(sizes["moe_topk"]),
                    float(sizes["routed_scaling_factor"]))
    assert combine.shape[-1] == real + int(sizes["zero_expert_num"]), combine.shape
    m = jnp.sum(combine[..., real:], axis=-1, keepdims=True) * u
    for e in range(moe["experts"]["gate"].shape[1]):      # real expert ``first + e``
        m = expert_add(m, u, combine[..., first + e], moe["experts"]["gate"][l, e],
                       moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
    return m


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, eps):
    return rms_norm(x, f32(scale), eps)


@jax.jit
def _head_block(x, w):
    return x @ f32(w)


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps = float(sizes["rms_norm_eps"])
    nope, rope, v_dim = (int(sizes[k]) for k in
                         ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    hidden = int(sizes["hidden_size"])
    q_scale = (hidden / int(sizes["q_lora_rank"])) ** 0.5 if sizes.get("mla_scale_q_lora") else 1.0
    kv_scale = (hidden / int(sizes["kv_lora_rank"])) ** 0.5 if sizes.get("mla_scale_kv_lora") else 1.0
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        cos, sin = rope_tables(ids.shape[1], rope, float(sizes["rope_theta"]))
        x = f32(model["embed"]["embedding"][ids])
        block = model["layers"]["block"]
        for l in range(block["sub_0"]["input_norm"]["scale"].shape[0]):
            m = None
            for i in (0, 1):
                sub = jax.tree.map(lambda a: a[l], block[f"sub_{i}"])      # noqa: B023
                x = x + attention(x, sub["input_norm"]["scale"], sub["attention"], cos, sin,
                                  eps, nope, v_dim, q_scale, kv_scale)
                u = _norm(x, sub["post_attn_norm"]["scale"], eps)
                if i == 0:
                    m = experts(u, block["moe"], l, sizes)
                x = x + mlp(u, sub["mlp"])
            x = x + m
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        x = _norm(x, model["final_norm"]["scale"], eps)
        w = params["lm_head"]["kernel"]
        return jnp.concatenate([_head_block(x, w[:, i: i + VOCAB_BLOCK])
                                for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)
