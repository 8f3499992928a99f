"""Several residual streams mixed by learned weights (manifold-constrained
hyper-connections, arXiv:2512.24880 over arXiv:2409.19606).

A token's state is ``X`` in ``R^{n x C}`` (``n`` streams of the hidden size)
where every other model here carries one ``x`` in ``R^C``. A sub-block ``F``
(an attention or an MLP, with its own input norm) is wrapped so:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + eps)                no learned gain
    H~_pre  = a_pre  * (x~ phi_pre)  + b_pre                     (n,)
    H~_post = a_post * (x~ phi_post) + b_post                    (n,)
    H~_res  = a_res  * mat(x~ phi_res) + b_res                   (n, n)
    H_pre   = sigmoid(H~_pre)        H_post = 2 sigmoid(H~_post)
    H_res   = Sinkhorn(exp(clip(H~_res, lo, hi))): ``iters`` times, every
              column over (its sum + eps), then every row likewise
    u       = sum_i H_pre[i] X[i]                                read
    X'[i]   = sum_j H_res[i, j] X[j] + H_post[i] F(u)            write

:class:`StreamMix` is the one module for it: ``coeff`` (the three projections
are ONE product, accumulated in float32, the norm's factor applied after it: a
scalar a token), ``read`` and ``write``. :func:`mhc_expand` / :func:`mhc_reduce`
are the seam ``models/llama.py::LlamaModel`` calls after the embedding and
before the final norm of a config with ``hc_mult``.

How it is written for the chip (measured on the v5e at n 4, C 3584, bf16;
PERF.md section 6, PR 58). The streams are carried as a TUPLE of ``n`` arrays
``(batch, tokens, C)`` in the model's dtype: the compiler laid a ``(batch,
tokens, n, C)`` carry out streams-major anyway, and putting the four written
streams back into one array cost a pass of its own over all of them (a
``concatenate``: 8C of 35C moved a token a sub-block). The coefficients live as
PLANES ``(.., batch, tokens)``, tokens on the minor axis: a 4 x 4 matrix a
token as its two minor axes fills a sixteenth of four sublanes of every
register it touches, and the ``n``-wide sums are then sums of planes, written
out (no ``reduce``: reductions over ``(.., n, n)`` read 9.8-16 us a sub-block in
a one-token step where this reads 6.1-6.3). The Sinkhorn's ``iters`` turns are
a ``fori_loop`` the compiler keeps as a loop of one small fused body: unrolled
into one chain it read 7.5-17 us a sub-block in a one-token step (the fewer the
rows the worse) and 861 us against 705 at 8 x 512 tokens. The chain, and the
mix's three steps, are module-level ``jax.jit`` functions whose traces a
program's sub-blocks share.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.parallel import mesh as ps

F32 = jnp.float32


def mhc_expand(x: jax.Array, n: int) -> Tuple[jax.Array, ...]:
    """``X_0[i] = x`` for every stream: (b, s, C) -> ``n`` times (b, s, C).
    Refused under tensor parallelism: no spec lays out the streams, and the
    latent attention under them is not partitioned either."""
    tp = ps.get_tensor_model_parallel_size() if ps.model_parallel_is_initialized() else 1
    if tp > 1:
        raise ValueError(f"hc_mult = {n} residual streams are not carried under tensor "
                         f"parallelism (tp = {tp}): no spec lays out the streams")
    with jax.named_scope("mhc_expand"):
        return (x,) * n


def mhc_reduce(streams: Tuple[jax.Array, ...]) -> jax.Array:
    """``h = sum_i X_L[i]``, summed in float32: (b, s, C)."""
    with jax.named_scope("mhc_reduce"):
        total = streams[0].astype(F32)
        for x_i in streams[1:]:
            total = total + x_i.astype(F32)
        return total.astype(streams[0].dtype)


@functools.partial(jax.jit, static_argnames=("iters", "eps"))
def sinkhorn_planes(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``m`` (n, n, ...) positive, float32, a matrix a trailing position:
    ``iters`` times every column over (its sum + eps), then every row
    likewise. No early stop: exactly ``iters`` turns."""
    n = m.shape[0]

    def turn(_, m):
        cols = m[0]
        for i in range(1, n):
            cols = cols + m[i]                       # (n, ...): sum over rows i
        m = m * (1.0 / (cols + eps))[None]
        rows = m[:, 0]
        for j in range(1, n):
            rows = rows + m[:, j]                    # (n, ...): sum over columns j
        return m * (1.0 / (rows + eps))[:, None]

    with jax.named_scope("mhc_sinkhorn"):
        return jax.lax.fori_loop(0, iters, turn, m)


def phi_init(key, shape, dtype=F32):
    """``phi`` (outputs, n, C), normal with variance 1 / (n C): ``x~ phi`` has
    unit spread whatever the widths."""
    return (jax.random.normal(key, shape, F32) / (shape[1] * shape[2]) ** 0.5).astype(dtype)


def beta_init(n: int):
    """The biases ``[b_pre | b_post | vec(b_res)]``: a unit normal everywhere
    and 3 more on ``b_res``'s diagonal, so that ``H_res`` is diagonal-heavy
    and not symmetric, ``H_pre`` far from uniform, and no stream is another's
    copy at the exit (with ``b = 0`` and a small ``a`` every ``H_res`` is the
    uniform matrix and the streams collapse to their mean)."""
    def init(key, shape, dtype=F32):
        diagonal = jnp.concatenate([jnp.zeros((2 * n,), F32), 3.0 * jnp.eye(n, dtype=F32).ravel()])
        return (jax.random.normal(key, shape, F32) + diagonal).astype(dtype)

    return init


@functools.partial(jax.jit, static_argnames=("iters", "eps", "clamp"))
def _coeff(x, phi, alpha, beta, iters, eps, clamp):
    n, width = len(x), x[0].shape[-1]
    with jax.named_scope("mhc_coeff"):
        raw = square = None
        for i in range(n):      # ``x`` is a tuple of n streams
            part = jnp.einsum("bsc,oc->bso", x[i], phi[:, i].astype(x[i].dtype),
                              preferred_element_type=F32)
            mass = jnp.sum(jnp.square(x[i].astype(F32)), axis=-1)
            raw, square = (part, mass) if raw is None else (raw + part, square + mass)
        # the norm's factor is one scalar a token: applied after the product
        raw = raw * jax.lax.rsqrt(square / (n * width) + eps)[..., None]
        planes = jnp.moveaxis(raw, -1, 0)                           # (n (n + 2), b, s)
        gain = jnp.concatenate([jnp.broadcast_to(alpha[k], (size,))
                                for k, size in enumerate((n, n, n * n))])
        planes = planes * gain[:, None, None] + beta[:, None, None]
        pre = jax.nn.sigmoid(planes[:n])
        post = 2.0 * jax.nn.sigmoid(planes[n: 2 * n])
        start = jnp.exp(jnp.clip(planes[2 * n:], *clamp)).reshape(n, n, *planes.shape[1:])
    return pre, post, sinkhorn_planes(start, iters, eps)


@jax.jit
def _read(x, pre):
    with jax.named_scope("mhc_read"):
        u = pre[0][..., None] * x[0].astype(F32)
        for i in range(1, len(x)):
            u = u + pre[i][..., None] * x[i].astype(F32)
        return u.astype(x[0].dtype)


@jax.jit
def _write(x, y, post, res):
    with jax.named_scope("mhc_write"):
        n = len(x)
        parts = [x[j].astype(F32) for j in range(n)]
        y = y.astype(F32)
        out = []
        for i in range(n):
            row = post[i][..., None] * y
            for j in range(n):
                row = row + res[i, j][..., None] * parts[j]
            out.append(row.astype(x[0].dtype))
        return tuple(out)


class StreamMix(nn.Module):
    """The mix around ONE sub-block: its own ``phi`` (n (n + 2), n, C), kept
    outputs-first so that no axis of 24 is a leaf's minor one; ``alpha`` (3,)
    the scalars ``a_pre, a_post, a_res``; ``beta`` (n (n + 2),) the biases.
    ``alpha`` and ``beta`` are float32 whatever the model's dtype. The three
    steps are module-level ``jax.jit`` functions: a program's four mixes (and
    flax's two passes over a scanned layer) share ONE trace of each, which on
    the serving host is seconds a program (PERF.md section 6, PR 58)."""

    streams: int
    hidden_size: int
    iters: int = 20
    eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = F32

    def setup(self):
        n = self.streams
        self.phi = self.param("phi", phi_init, (n * (n + 2), n, self.hidden_size),
                              self.param_dtype)
        self.alpha = self.param("alpha", nn.initializers.ones, (3,), F32)
        self.beta = self.param("beta", beta_init(n), (n * (n + 2),), F32)

    def coeff(self, x: Tuple[jax.Array, ...]):
        """``(H_pre (n, b, s), H_post (n, b, s), H_res (n, n, b, s))`` float32
        of the streams ``x``, ``n`` of (b, s, C)."""
        return _coeff(tuple(x), self.phi, self.alpha, self.beta, self.iters, self.eps,
                      tuple(self.clamp))

    def read(self, x: Tuple[jax.Array, ...], pre: jax.Array) -> jax.Array:
        """``u = sum_i H_pre[i] X[i]``: (b, s, C) in the model's dtype."""
        return _read(tuple(x), pre)

    def write(self, x: Tuple[jax.Array, ...], y: jax.Array, post: jax.Array,
              res: jax.Array) -> Tuple[jax.Array, ...]:
        """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``."""
        return _write(tuple(x), y, post, res)
