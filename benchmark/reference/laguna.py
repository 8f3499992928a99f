"""Plain reference of Laguna (``model_type: laguna``; poolside/Laguna-S-2.1).

Decoder layer ``l``, written from the equations of ISSUE 49 (the published
config fixes every shape and structural choice; ``transformers`` 4.57.6 has no
``laguna`` model, and the three pointwise functions the config has no key for
are VALUES in the configuration file's ``assumed``: ``scoring_func``,
``gating``, no QK-norm). All linears without bias, RMSNorm with
``rms_norm_eps``, ``u = RMSNorm(x)``:

    H_l = num_attention_heads_per_layer[l], kind = layer_types[l]
    q = u Wq (H_l, hd);  k = u Wk, v = u Wv (n_kv, hd)
    full_attention:    dims 0 .. r-1 of each head rotate, r = hd *
      partial_rotary_factor, by YaRN frequencies computed FOR r dims (factor,
      original_max_position_embeddings, beta_fast / beta_slow, rope_theta of
      ``rope_parameters.full_attention``), cos and sin times the given
      ``attention_factor``; dims r .. hd-1 pass
    sliding_attention: plain rope over all hd dims, its own rope_theta
    score[h] = q[h] . k[h // (H_l / n_kv)] / sqrt(hd)
    mask: key j is seen from position i iff j <= i, and on a sliding layer
      also j > i - sliding_window (the token and the 511 before it)
    a = softmax(score) v in float32;  g = sigmoid(u Wg) (H_l,), one scalar a
      head a token (``gating: per-head``);  x' = x + concat_h(g_h a_h) Wo
    y = x' + FFN_l(RMSNorm(x'))
      l in mlp_only_layers: down(silu(gate z) * up z)
      the rest: s = sigmoid(z Wr) (``scoring_func``; softmax where the file
      says so) over ALL routed experts, float32; T = the top
      ``num_experts_per_tok`` by s; w_e = moe_routed_scaling_factor * s_e /
      sum_T s (``norm_topk_prob``);
      FFN(z) = SwiGLU_shared(z) + sum_{e in T} w_e SwiGLU_e(z)
    logits = RMSNorm(y_last) W_head      (untied head)

The chip's share of the experts: the router is as wide as published
(``sizes["router_experts"]``) and chooses among all of them, the parameter
tree holds experts ``experts_held_first .. + held`` only, and what the absent
experts would have added is left out, here as in the program
(``/opt/skills/guides/model-configs`` section 4). With all of them held this
is the uncut model.

float32 throughout, ``default_matmul_precision("highest")``; no kernel, no
cache, no ring: a dense (T, T) mask a layer. Departure from the published
layout: rope pairs ``(i, i + r/2)`` as the program's ``apply_rotary`` (a fixed
permutation of ``W_q`` / ``W_k`` columns against a ``(2i, 2i + 1)``
checkpoint, as ``deepseek_v2.py`` notes of its own).

Memory: the layout is the PROGRAM's parameter tree (``model.first.block``,
stacked over one layer, and ``model.periods.<kind>_<place>``, stacked over
the periods), walked one layer and one expert at a time; attention runs one
row and one block of heads at a time (scores of 4 heads over 4100 tokens are
0.27 GB); the head is multiplied in vocabulary blocks.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, f32
from .deepseek_v2 import (
    _expert_add,
    _final_norm,
    _head_block,
    _mlp_add,
    rms_norm,
    rotate,
    yarn_inv_freq,
)

HEAD_BLOCK = 4
VOCAB_BLOCK = 25088
FULL, SLIDING = "full_attention", "sliding_attention"


def rope_tables(seq: int, head_dim: int, rope: dict):
    """cos, sin (seq, r / 2) float32 of one kind's ``rope_parameters`` entry,
    ``r = head_dim * partial_rotary_factor`` the dims that rotate."""
    r = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "yarn":
        inv, amp = yarn_inv_freq(r, theta, rope), float(rope["attention_factor"])
    else:
        inv = (1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)).astype(np.float32)
        amp = 1.0
    ang = np.arange(seq, dtype=np.float32)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang) * amp, F32), jnp.asarray(np.sin(ang) * amp, F32)


@partial(jax.jit, static_argnames=("eps", "n_kv", "window", "gated"))
def _attend(x, blk, cos, sin, eps, n_kv, window, gated=True):
    """(h, z): the residual after the gated attention, and its norm for the
    FFN. ``window`` None: a full layer; ``gated`` False: no gate (g = 1)."""
    blk = f32(blk)
    att = blk["attention"]
    u = rms_norm(x, blk["input_norm"]["scale"], eps)
    wq, wk, wv = (att["qkv"][n] for n in ("q_kernel", "k_kernel", "v_kernel"))
    n, hd = wq.shape[1:]
    s = x.shape[1]
    r = 2 * cos.shape[-1]
    q = jnp.einsum("bsh,hnd->bsnd", u, wq)
    k = jnp.einsum("bsh,hnd->bsnd", u, wk)
    v = jnp.einsum("bsh,hnd->bsnd", u, wv)
    gate = (jax.nn.sigmoid(u @ att["gate_kernel"]) if gated                # (b, s, n)
            else jnp.ones((*u.shape[:2], n), F32))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)

    def turn(t):                        # (s, heads, hd): the first r dims rotate
        return jnp.concatenate([rotate(t[..., :r], cos, sin), t[..., r:]], axis=-1)

    def one_row(row):
        q1, k1, v1 = row                                                   # (s, ...)
        q1, k1 = turn(q1), turn(k1)
        k1 = jnp.repeat(k1, n // n_kv, axis=1)                             # head h on h // group
        v1 = jnp.repeat(v1, n // n_kv, axis=1)

        def heads(qkv):
            qh, kh, vh = qkv                                               # (s, block, hd)
            score = jnp.einsum("qnd,knd->nqk", qh, kh) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
            return jnp.einsum("nqk,knd->qnd", p, vh)

        block = math.gcd(HEAD_BLOCK, n)
        split = lambda t: t.reshape(s, n // block, block, hd).transpose(1, 0, 2, 3)  # noqa: E731
        o = jax.lax.map(heads, (split(q1), split(k1), split(v1)))          # (n / block, s, block, hd)
        return o.transpose(1, 0, 2, 3).reshape(s, n, hd)

    a = jax.lax.map(one_row, (q, k, v)) * gate[..., None]
    h = x + a.reshape(*x.shape[:2], n * hd) @ att["o_proj"]["kernel"]
    return h, rms_norm(h, blk["post_attn_norm"]["scale"], eps)


@partial(jax.jit, static_argnames=("top_k", "scoring", "renormalise", "scale"))
def route(z, router, top_k, scoring, renormalise, scale):
    """(tokens, ALL routed experts) weights: each expert's score in float32,
    the top-k by score, their scores renormalised to sum 1 and scaled."""
    logits = z @ f32(router)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(scores, top_k)
    kept = scores * jnp.sum(jax.nn.one_hot(topi, scores.shape[-1], dtype=F32), axis=-2)
    if renormalise:
        kept = kept / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return kept * scale


def layers_of(params, sizes):
    """``(kind, dense?, the layer's parameters STACKED as the program holds
    them, its index in that stack)`` of every layer in order:
    ``model.first.block`` (the leading dense layer, stacked over one) and
    ``model.periods.<kind>_<place>`` (stacked over the periods). The caller
    slices what it multiplies when it multiplies it: every layer sliced at
    once would be a second copy of the model."""
    n = int(sizes["num_hidden_layers"])
    types = list(sizes["layer_types"])[:n]
    dense = len(sizes.get("mlp_only_layers", [0]))
    model = params["model"]
    for l in range(dense):
        yield types[l], True, model["first"]["block"], l
    names = sorted(model["periods"], key=lambda name: int(name.rsplit("_", 1)[1]))
    for p in range((n - dense) // len(names)):
        for at, name in enumerate(names):
            kind = types[dense + p * len(names) + at]
            assert name == f"{kind}_{at}", (name, kind, at)
            yield kind, False, model["periods"][name], p


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps = float(sizes["rms_norm_eps"])
    hd, n_kv = int(sizes["head_dim"]), int(sizes["num_key_value_heads"])
    first = int(sizes.get("experts_held_first", 0))
    scoring = sizes.get("scoring_func", "sigmoid")
    gating = sizes.get("gating", "per-head")
    if gating not in ("per-head", "none"):
        raise ValueError(f"gating {gating!r}: this reference gates per head, or not at all")
    with jax.default_matmul_precision("highest"):
        tables = {kind: rope_tables(ids.shape[1], hd, rope)
                  for kind, rope in sizes["rope_parameters"].items()}
        x = f32(params["model"]["embed"]["embedding"][ids])
        for kind, dense, blk, l in layers_of(params, sizes):
            own = lambda tree: jax.tree.map(lambda a: a[l], tree)  # noqa: E731
            light = {k: own(blk[k]) for k in ("input_norm", "attention", "post_attn_norm")}
            h, z = _attend(x, light, *tables[kind], eps, n_kv,
                           int(sizes["sliding_window"]) if kind == SLIDING else None,
                           gating == "per-head")
            if dense:
                x = _mlp_add(h, z, own(blk["mlp"]))
                continue
            moe = blk["moe"]
            combine = route(z, moe["router"]["kernel"][l], int(sizes["num_experts_per_tok"]),
                            scoring, bool(sizes.get("norm_topk_prob", True)),
                            float(sizes.get("moe_routed_scaling_factor", 1.0)))
            x = _mlp_add(h, z, own(blk["shared_expert"]))
            for e in range(moe["experts"]["gate"].shape[1]):   # expert ``first + e`` of the router's
                x = _expert_add(x, z, combine[..., first + e], moe["experts"]["gate"][l, e],
                                moe["experts"]["up"][l, e], moe["experts"]["down"][l, e])
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        x = _final_norm(x, params["model"]["final_norm"]["scale"], eps)
        w = params["lm_head"]["kernel"]
        return jnp.concatenate([_head_block(x, w[:, i: i + VOCAB_BLOCK])
                                for i in range(0, w.shape[1], VOCAB_BLOCK)], axis=-1)
