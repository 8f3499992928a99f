"""Plain reference of GPT-NeoX (Pythia): forward pass and loss.

Decoder layer, from the published modelling code (transformers
``modeling_gpt_neox.py``, ``use_parallel_residual=True``):

    y = x + Attn(LN1(x)) + MLP(LN2(x))

LayerNorm with bias; q, k, v, o, up and down projections with bias; rotary on
the first ``rotary_pct`` of each head's dimensions with
``inv_freq_i = base ** (-2i / rotary_dims)``; MLP ``down(gelu(up(z)))`` with
the EXACT (erf) GELU, which is what Pythia's ``hidden_act: "gelu"`` names;
final LayerNorm; untied output head without bias. The loss is the mean
cross-entropy over all positions against labels already shifted by the caller
(``labels[i]`` is the token after position i).

float32, ``default_matmul_precision("highest")``, one layer of the program's
stacked parameter tree at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, causal_attention, f32, rotary


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["ln"]["scale"] + p["ln"]["bias"]


from functools import partial


@partial(jax.jit, static_argnames=("eps", "rot", "base"))
def _layer(x, blk, eps, rot, base):
    blk = f32(blk)
    qkv = blk["attention"]["qkv"]
    z = layer_norm(x, blk["input_norm"], eps)
    q = jnp.einsum("bsh,hnd->bsnd", z, qkv["q_kernel"]) + qkv["q_bias"]
    k = jnp.einsum("bsh,hnd->bsnd", z, qkv["k_kernel"]) + qkv["k_bias"]
    v = jnp.einsum("bsh,hnd->bsnd", z, qkv["v_kernel"]) + qkv["v_bias"]
    q, k = rotary(q, base, rot), rotary(k, base, rot)
    o = blk["attention"]["o_proj"]
    attn = causal_attention(q, k, v).reshape(*x.shape[:2], -1) @ o["kernel"] + o["bias"]
    m = layer_norm(x, blk["post_attn_norm"], eps)
    up, down = blk["mlp"]["up"], blk["mlp"]["down"]
    mlp = jax.nn.gelu(m @ up["kernel"] + up["bias"], approximate=False) @ down["kernel"] + down["bias"]
    return x + attn + mlp


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, w, eps):
    return layer_norm(x, f32(final_norm), eps) @ f32(w)


@jax.jit
def _nll_sum(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def forward(params, ids, sizes) -> jax.Array:
    """Logits (b, s, vocab), float32."""
    eps = float(sizes["layer_norm_eps"])
    heads = sizes["num_attention_heads"]
    rot = int(sizes["hidden_size"] // heads * sizes["rotary_pct"])
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        block = model["layers"]["block"]
        x = jnp.asarray(model["embed"]["embedding"], F32)[ids]
        layers = block["input_norm"]["ln"]["scale"].shape[0]
        for l in range(layers):
            x = _layer(x, jax.tree.map(lambda a: a[l], block), eps, rot,
                       float(sizes["rotary_emb_base"]))
        return _head(x, model["final_norm"], params["lm_head"]["kernel"], eps)


def loss(params, ids, labels, sizes) -> jax.Array:
    """Mean next-token cross-entropy, float32, one sequence's logits at a
    time (a whole batch of (2048, 50432) float32 rows is 3.3 GB)."""
    with jax.default_matmul_precision("highest"):
        total = jnp.zeros((), F32)
        for i in range(ids.shape[0]):
            total = total + _nll_sum(forward(params, ids[i: i + 1], sizes)[0], labels[i])
        return total / labels.size
