"""Pallas fused LayerNorm (fwd + custom-vjp bwd).

Parity: the reference's fused layer-norm CUDA kernels (csrc/transformer
fused_ln / inference layer_norm). Same single-VMEM-pass structure as the
RMSNorm kernel next door (rmsnorm.py): one row-block pass computes mean,
variance, and the affine output in fp32; backward recomputes rstd and fuses
dx with the dscale/dbias row-reductions, accumulating the latter across the
sequential TPU grid into one (8, D) block. BLOOM and GPT-2 are the LayerNorm
model families (models/transformer.py:190).

Layout: x [..., D] flattened to [rows, D]; D padded to 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .rmsnorm import _block_rows, _interpret, _pad_rows


def _fwd_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    o_ref[:] = (
        xhat * s_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    ).astype(o_ref.dtype)


def _bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, db_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    s = s_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    gs = g * s
    # dx = rstd * (gs - mean(gs) - xhat * mean(gs * xhat))
    m1 = jnp.mean(gs, axis=-1, keepdims=True)
    m2 = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gs - m1 - xhat * m2)).astype(dx_ref.dtype)

    # dscale/dbias: TPU grid runs sequentially — accumulate into one (8, D)
    # block (min sublane tile); host reads row 0
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        ds_ref[:] = jnp.zeros_like(ds_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    ds_part = jnp.sum(g * xhat, axis=0, keepdims=True)  # (1, D)
    db_part = jnp.sum(g, axis=0, keepdims=True)  # (1, D)
    ds_ref[:] = ds_ref[:] + jnp.broadcast_to(ds_part, ds_ref.shape)
    db_ref[:] = db_ref[:] + jnp.broadcast_to(db_part, db_ref.shape)


def _run_fwd(x2, scale, bias, eps):
    block = _block_rows(*x2.shape)
    x2, valid_rows = _pad_rows(x2, block)
    rows, D = x2.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x2.dtype),
        interpret=_interpret(),
    )(x2, scale.reshape(1, D), bias.reshape(1, D))[:valid_rows]


def _run_bwd(x2, scale, g2, eps):
    block = _block_rows(*x2.shape)
    x2, valid_rows = _pad_rows(x2, block)
    g2, _ = _pad_rows(g2, block)
    rows, D = x2.shape
    dx, ds_acc, db_acc = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((block, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (0, 0)),
            pl.BlockSpec((8, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, D), x2.dtype),
            jax.ShapeDtypeStruct((8, D), jnp.float32),
            jax.ShapeDtypeStruct((8, D), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, scale.reshape(1, D), g2)
    return dx[:valid_rows], ds_acc[0], db_acc[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layernorm(x, scale, bias, eps: float = 1e-5):
    """Fused LayerNorm over the last dim. x [..., D], scale/bias [D]."""
    out, _ = _layernorm_fwd(x, scale, bias, eps)
    return out


def _layernorm_fwd(x, scale, bias, eps):
    shape = x.shape
    out = _run_fwd(x.reshape(-1, shape[-1]), scale, bias, eps)
    return out.reshape(shape), (x, scale)


def _layernorm_bwd(eps, res, g):
    x, scale = res
    shape = x.shape
    dx, ds, db = _run_bwd(
        x.reshape(-1, shape[-1]), scale, g.reshape(-1, shape[-1]), eps
    )
    return dx.reshape(shape), ds.astype(scale.dtype), db.astype(scale.dtype)


layernorm.defvjp(lambda x, s, b, eps: _layernorm_fwd(x, s, b, eps),
                 _layernorm_bwd)
