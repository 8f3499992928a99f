"""Pieces both plain references share: rotary tables and causal attention.

Plain ``jax.numpy`` in float32, written from the published equations; imports
nothing from the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def rotary(x, theta: float, rot_dims: int):
    """Rotate the first ``rot_dims`` of each head of ``x`` (b, s, n, d) by
    position, in the ``rotate_half`` convention of the published code:
    inv_freq_i = theta ** (-2i / rot_dims), pairs (i, i + rot_dims / 2)."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dims, 2, dtype=F32) / rot_dims))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]          # (s, rot/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    rot, rest = x[..., :rot_dims], x[..., rot_dims:]
    x1, x2 = rot[..., : rot_dims // 2], rot[..., rot_dims // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, rest], axis=-1)


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(d)) v under a causal mask. q (b, s, n, d);
    k, v (b, s, n_kv, d): query head i reads key/value head i // (n / n_kv).
    One sequence at a time, so that only (n, s, s) scores are alive."""
    n, nkv, d = q.shape[2], k.shape[2], q.shape[3]

    def one(qkv):
        q1, k1, v1 = qkv                                           # (s, n, d) ...
        k1 = jnp.repeat(k1, n // nkv, axis=1)
        v1 = jnp.repeat(v1, n // nkv, axis=1)
        scores = jnp.einsum("qnd,knd->nqk", q1, k1) / jnp.sqrt(F32(d))
        s = q1.shape[0]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v1)

    return jax.lax.map(one, (q, k, v))
