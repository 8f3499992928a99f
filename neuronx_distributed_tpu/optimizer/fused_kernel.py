"""Single-pass Pallas AdamW update kernel.

Role: the reference's optimizer hot loop (``utils/adamw_fp32_optim_params.py``
``step``:91) is elementwise math over four param-sized buffers (grad, mu, nu,
fp32 master). XLA fuses the chain well but still materializes the fp32 grad
cast and schedules the update as several loops; measured on-chip the
optimizer+clip stage ran ~44 ms against a ~24 ms HBM roofline (PROFILE.md).
This kernel does the whole update in ONE pass per leaf: read g (bf16),
mu, nu, master (fp32); write mu, nu, master, and the bf16 param — exactly
the roofline's traffic, nothing else. The clip scale and the step's
lr/bias-correction scalars ride in as a tiny (1, 4) fp32 operand.

Leaves whose size doesn't tile (small biases/norms) stay on the jnp path —
their bytes are negligible. On non-TPU backends the kernel runs under the
Pallas interpreter, so CPU tests exercise the real code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from neuronx_distributed_tpu.kernels import mode

_W = 1024          # lane-dim width of the flattened view (8 sublanes x 128)
_MAX_ROWS = 128    # rows per block: 4 fp32 refs x 0.5 MB + outputs < VMEM


def _kernel(s_ref, g_ref, mu_ref, nu_ref, ms_ref,
            mu_o, nu_o, ms_o, p_o, *, b1, b2, eps, wd):
    scale = s_ref[0, 0]
    lr = s_ref[0, 1]
    bc1 = s_ref[0, 2]
    bc2 = s_ref[0, 3]
    g = g_ref[...].astype(jnp.float32) * scale
    mu = b1 * mu_ref[...] + (1.0 - b1) * g
    nu = b2 * nu_ref[...] + (1.0 - b2) * g * g
    ms = ms_ref[...]
    ms = ms - lr * ((mu / bc1) / (jnp.sqrt(nu / bc2) + eps) + wd * ms)
    mu_o[...] = mu
    nu_o[...] = nu
    ms_o[...] = ms
    p_o[...] = ms.astype(p_o.dtype)


def leaf_supported(n: int) -> bool:
    """Tileable: flattens to (rows, 1024) with rows divisible by 8."""
    return n >= 8 * _W and n % (8 * _W) == 0


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "p_dtype"))
def fused_adamw_leaf(g, mu, nu, ms, scalars, *, b1, b2, eps, wd, p_dtype):
    """One leaf's update: returns (mu', nu', master', param').

    ``scalars`` is a (1, 4) fp32 array [clip_scale, lr, bias_corr1,
    bias_corr2]. Buffers are aliased in/out (mu, nu, master update in place).
    """
    n = g.size
    rows = n // _W
    br = _MAX_ROWS
    while rows % br:
        br //= 2
    shape2 = (rows, _W)
    g2 = g.reshape(shape2)
    mu2 = mu.reshape(shape2)
    nu2 = nu.reshape(shape2)
    ms2 = ms.reshape(shape2)
    grid = (rows // br,)
    blk = pl.BlockSpec((br, _W), lambda i: (i, 0))
    sblk = pl.BlockSpec((1, 4), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[sblk, blk, blk, blk, blk],
        out_specs=[blk, blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, p_dtype),
        ],
        # mu/nu/master update in place (operand i=2,3,4 -> output 0,1,2)
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=mode.interpret_kernels(),
        name="fused_adamw",
    )(scalars, g2, mu2, nu2, ms2)
    mu_n, nu_n, ms_n, p_n = out
    return (mu_n.reshape(mu.shape), nu_n.reshape(nu.shape),
            ms_n.reshape(ms.shape), p_n.reshape(g.shape))
