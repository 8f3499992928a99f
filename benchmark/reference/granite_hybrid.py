"""Plain reference of Granite-4.0-H (``model_type: granitemoehybrid``).

From the published modelling code (transformers
``modeling_granitemoehybrid.py`` of ibm-granite/granite-4.0-h-micro; the
Mamba-2 mixer as in ``modeling_bamba.py`` / the ``mamba_ssm`` package), with
``d_inner = mamba_n_heads x mamba_d_head``, one B/C group, ``N =
mamba_d_state``, ``K = mamba_d_conv``; all linears without bias, RMSNorm with
``rms_norm_eps``:

    h0 = embedding_multiplier * embed(ids)
    every layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
                  x = x + residual_multiplier * W_down(silu(W_gate z) * W_up z),
                      z = RMSNorm(x)   (``shared_intermediate_size`` wide; the
                      config has no routed experts: ``num_local_experts`` 0)
    ``layer_types[l] == "attention"``: GQA, q and k NOT rotated
      (``position_embedding_type: nope``), scores scaled by
      ``attention_multiplier`` (not 1 / sqrt(d)), causal softmax.
    ``layer_types[l] == "mamba"``:
      [z | xBC | dt] = u W_in        widths d_inner, d_inner + 2 N, heads
      xBC_t = silu(b_c + sum_{j < K} w_c[j] * xBC_{t - K + 1 + j})   depthwise,
                                      causal, zeros before the sequence
      [x | B | C] = xBC              x: heads x d_head
      dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)             a head
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t                   (head, d_head, N)
      y_t = S_t C_t + D x_t
      y = RMSNorm(y * silu(z)) * w_norm   over all d_inner (one group)
      out = y W_out
    logits = embed^T RMSNorm(x) / logits_scaling          (tied)

The recurrence is a ``lax.scan`` over TOKENS, one token a turn: the program's
prompt path takes the chunked (SSD) form, so the comparison is what shows the
two derivations agree. ``time_step_limit`` is (0, inf): nothing is clamped.

float32 throughout, ``default_matmul_precision("highest")``. The layout is
the PROGRAM's parameter tree: ``model.periods`` holds one entry a layer of a
period, ``mamba_<position>`` or ``attention_<position>``, with leaves stacked
over the periods; the convolution's kernel is stored ``(K, channels)`` and
``W_in``'s columns as ``in_proj`` ([z | xBC]) and ``dt_proj`` (dt). One layer is
widened to float32 at a time, so the reference fits beside the bf16 model.
Imports nothing from the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import F32

VOCAB_BLOCK = 25088
# None, or a dtype every weight is rounded to before it is widened to float32:
# the control that a tolerance has to refuse (float8_e4m3fn, the nearest
# precision below the bf16 the configuration states); set before the first call
ROUND_TO = None


def widen(tree):
    def one(a):
        a = jnp.asarray(a)
        return (a.astype(ROUND_TO) if ROUND_TO is not None else a).astype(F32)
    return jax.tree.map(one, tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def period_of(types) -> int:
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and all(types[i] == types[i % p] for i in range(n)))


def _swiglu(z, mlp):
    return ((jax.nn.silu(z @ mlp["gate_proj"]["kernel"]) * (z @ mlp["up_proj"]["kernel"]))
            @ mlp["down_proj"]["kernel"])


def _attention(u, w, scale):
    q = jnp.einsum("bsh,hnd->bsnd", u, w["q_kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, w["k_kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, w["v_kernel"])
    n, nkv, s = q.shape[2], k.shape[2], q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv):           # one sequence at a time: (n, s, s) scores alive
        q1, k1, v1 = qkv
        k1, v1 = jnp.repeat(k1, n // nkv, axis=1), jnp.repeat(v1, n // nkv, axis=1)
        scores = jnp.einsum("qnd,knd->nqk", q1, k1) * scale
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v1)

    o = jax.lax.map(one, (q, k, v))
    return o.reshape(*o.shape[:2], -1)


def _mamba(u, w, heads, d_head, d_state, eps):
    b, s, _ = u.shape
    d_inner = heads * d_head
    # the program keeps the published in_proj's columns as [z | xBC] and dt
    z, xbc = jnp.split(u @ w["in_proj"]["kernel"], [d_inner], axis=-1)
    dt = u @ w["dt_proj"]["kernel"]
    taps = w["conv_kernel"]                                         # (K, channels)
    k = taps.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[:, j: j + s] for j in range(k))
    if "conv_bias" in w:
        conv = conv + w["conv_bias"]
    x, B, C = jnp.split(jax.nn.silu(conv), [d_inner, d_inner + d_state], axis=-1)
    x = x.reshape(b, s, heads, d_head)
    dt = jax.nn.softplus(dt + w["dt_bias"])                         # (b, s, heads)
    A = -jnp.exp(w["A_log"])

    def token(S, t):
        x_t, B_t, C_t, dt_t = t                                     # (b, h, p), (b, n), (b, n), (b, h)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        return S, jnp.einsum("bhpn,bn->bhp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((b, heads, d_head, d_state), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    return rms_norm(y, w["norm"], eps) @ w["out_proj"]


@partial(jax.jit, static_argnames=("kind", "heads", "d_head", "d_state", "eps", "scale",
                                   "residual"))
def _layer(x, blk, kind, heads, d_head, d_state, eps, scale, residual):
    blk = widen(blk)
    u = rms_norm(x, blk["input_norm"]["scale"], eps)
    if kind == "mamba":
        mixed = _mamba(u, blk["mamba"], heads, d_head, d_state, eps)
    else:
        mixed = _attention(u, blk["attention"]["qkv"], scale) @ blk["attention"]["o_proj"]["kernel"]
    x = x + residual * mixed
    z = rms_norm(x, blk["post_mixer_norm"]["scale"], eps)
    return x + residual * _swiglu(z, blk["mlp"])


@partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, eps):
    return rms_norm(x, widen(scale), eps)


@jax.jit
def _head_block(x, rows):
    return x @ widen(rows).T


def forward(params, ids, sizes, positions=None) -> jax.Array:
    """Logits (b, s, vocab) in float32 of the full causal forward pass; with
    ``positions`` (b, k), only those positions go through the output head."""
    eps = float(sizes["rms_norm_eps"])
    types = list(sizes["layer_types"])
    period = period_of(types)
    static = dict(heads=int(sizes["mamba_n_heads"]), d_head=int(sizes["mamba_d_head"]),
                  d_state=int(sizes["mamba_d_state"]), eps=eps,
                  scale=float(sizes["attention_multiplier"]),
                  residual=float(sizes["residual_multiplier"]))
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        stack = model["periods"]
        table = model["embed"]["embedding"]
        x = widen(table[ids]) * float(sizes["embedding_multiplier"])
        for l, kind in enumerate(types):
            rep, pos = divmod(l, period)
            blk = jax.tree.map(lambda a: a[rep], stack[f"{kind}_{pos}"])
            x = _layer(x, blk, kind=kind, **static)
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
        x = _normed(x, model["final_norm"]["scale"], eps)
        head = table if sizes.get("tie_word_embeddings", True) else params["lm_head"]["kernel"].T
        logits = jnp.concatenate([_head_block(x, head[v: v + VOCAB_BLOCK])
                                  for v in range(0, head.shape[0], VOCAB_BLOCK)], axis=-1)
        return logits / float(sizes["logits_scaling"])
