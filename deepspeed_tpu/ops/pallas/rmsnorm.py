"""Pallas fused RMSNorm (fwd + custom-vjp bwd).

Parity: csrc/transformer layer-norm kernels (the reference fuses norm into
its transformer CUDA blocks). One VMEM pass per row-block computes the
mean-square and the normalized output; backward recomputes rstd and fuses
dx/dscale. XLA already fuses simple norms well, so the payoff is on long
rows (hidden >= 4k) where the fp32 accumulation + single HBM pass matters.

Layout: x [..., D] flattened to [rows, D]; D padded to 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# fp32 elements one row block may hold. The backward keeps about a dozen
# block-sized fp32 values live next to the double-buffered operands, and the
# chip's compiler refuses the kernel once they outgrow VMEM: 256 rows compile
# at D=1024 and not at D=4096, so the row block shrinks as D grows.
_BLOCK_ELEMS = 256 * 1024


def _fwd_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    s = s_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    D = x.shape[-1]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = x * rstd
    gs = g * s
    # dx = rstd * (gs - xhat * mean(gs * xhat))
    dot = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gs - xhat * dot)).astype(dx_ref.dtype)
    # dscale: TPU grid runs sequentially, so accumulate into one (8, D)
    # block (min sublane tile); host reads row 0
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    partial = jnp.sum(g * xhat, axis=0, keepdims=True)  # (1, D)
    ds_ref[:] = ds_ref[:] + jnp.broadcast_to(partial, ds_ref.shape)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_rows(rows: int, D: int) -> int:
    """Rows per grid step, from the shape alone: at most ``_BLOCK_ELEMS``
    elements, a multiple of the 16-row bf16 sublane tile, never more than
    the array has (a block equal to the full dim is always legal)."""
    return min(rows, max(16, _BLOCK_ELEMS // D // 16 * 16))


def _pad_rows(a, block):
    """Zero-pad rows to a whole number of blocks: zero rows contribute zero
    to the dscale partial (g=0), so no masking is needed in-kernel."""
    rows = a.shape[0]
    pad = (-rows) % block
    return (jnp.pad(a, ((0, pad), (0, 0))) if pad else a), rows


def _run_fwd(x2, scale, eps):
    block = _block_rows(*x2.shape)
    x2, valid_rows = _pad_rows(x2, block)
    rows, D = x2.shape
    grid = (rows // block,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x2.dtype),
        interpret=_interpret(),
    )(x2, scale.reshape(1, D))[:valid_rows]


def _run_bwd(x2, scale, g2, eps):
    block = _block_rows(*x2.shape)
    x2, valid_rows = _pad_rows(x2, block)
    g2, _ = _pad_rows(g2, block)
    rows, D = x2.shape
    nblocks = rows // block
    dx, ds_acc = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((block, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, D), lambda i: (i, 0)),
            pl.BlockSpec((8, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, D), x2.dtype),
            jax.ShapeDtypeStruct((8, D), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, scale.reshape(1, D), g2)
    return dx[:valid_rows], ds_acc[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, scale, eps: float = 1e-5):
    """Fused RMSNorm over the last dim. x [..., D], scale [D]."""
    out, _ = _rmsnorm_fwd(x, scale, eps)
    return out


def _rmsnorm_fwd(x, scale, eps):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _run_fwd(x2, scale, eps)
    return out.reshape(shape), (x, scale)


def _rmsnorm_bwd(eps, res, g):
    x, scale = res
    shape = x.shape
    dx, ds = _run_bwd(
        x.reshape(-1, shape[-1]), scale, g.reshape(-1, shape[-1]), eps
    )
    return dx.reshape(shape), ds.astype(scale.dtype)


rmsnorm.defvjp(lambda x, s, eps: _rmsnorm_fwd(x, s, eps), _rmsnorm_bwd)
