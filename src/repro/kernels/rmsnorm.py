"""Fused RMSNorm Pallas kernel — row-tiled, single pass in VMEM."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (bn, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 256,
            interpret: Optional[bool] = None):
    """x: (..., D) -> same shape; scale: (D,).  ``interpret=None`` picks
    the mode from the backend (:func:`interpret_default`)."""
    if interpret is None:
        interpret = interpret_default()
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    bn = min(block_rows, N)
    if N % bn:
        pad = bn - N % bn
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), xf.dtype)], 0)
    grid = (xf.shape[0] // bn,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, scale)
    return out[:N].reshape(orig_shape)
