"""Chunked WKV6 Pallas kernel (the RWKV6 recurrence, TPU target).

Naive WKV6 is a length-T sequential scan — hostile to the MXU.  This
kernel processes the sequence in chunks of C tokens:

  within a chunk, pairwise decay factors exp(cum_{t-1} - cum_s) (all <= 1,
  numerically safe) give a (C,C) score matrix and one (C,C)x(C,D)
  matmul; the carried (D,D) state contributes via one (C,D)x(D,D)
  matmul; the state update is another matmul with relative decays <= 1.

Grid = (B, H, T/C) with the chunk dim innermost; the f32 (D,D) state lives
in VMEM scratch and persists across chunk iterations (TPU sequential grid).
The updated state is emitted on the last chunk.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

_EXACT = jax.lax.Precision.HIGHEST


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            state, *, C: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)          # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, D)

    logw = jnp.log(jnp.maximum(w, 1e-30))        # (C, D), <= 0
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # inclusive decay logs: Mosaic has no cumsum, so the prefix sum is a
    # lower-triangular matmul, at full f32 precision since it is
    # exponentiated below
    tri = (s_idx <= t_idx).astype(jnp.float32)
    cum = jnp.dot(tri, logw, precision=_EXACT)   # (C, D)
    cum_prev = cum - logw                        # cum_{t-1}

    s_prev = state[...]                          # (D, D) = (k-dim, v-dim)
    # inter-chunk: o_t += (r_t * P_{t-1}) @ S_prev
    inter = (r * jnp.exp(cum_prev)) @ s_prev     # (C, Dv)

    # intra-chunk: scores[t,s] = sum_d r_t k_s exp(cum_{t-1} - cum_s), s<t,
    # built one column s at a time: Mosaic cannot lower the einsum over
    # a (C, C, D) decay tensor.  Exponents are clipped at 0 so the masked t <= s lanes
    # cannot overflow; the u-bonus fills the diagonal (s == t).
    t_col = t_idx[:, :1]                                  # (C, 1)
    scores = jnp.where(s_idx == t_idx,
                       jnp.sum(r * u * k, axis=-1, keepdims=True), 0.0)
    for s in range(C - 1):
        e = jnp.exp(jnp.minimum(cum_prev - cum[s:s + 1, :], 0.0))
        col = jnp.sum(r * e * k[s:s + 1, :], axis=-1, keepdims=True)
        scores = scores + jnp.where((s_idx == s) & (t_col > s), col, 0.0)
    intra = scores @ v                                    # (C, Dv)

    o_ref[0, 0] = (inter + intra).astype(o_ref.dtype)

    # state update: S_new = diag(P_C) S + sum_s (P_C / P_s) k_s (x) v_s
    D = s_prev.shape[0]
    cum_last = cum[C - 1:C, :]                            # (1, D)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (D, D), 1))
    pc_diag = jnp.where(eye, jnp.exp(cum_last), 0.0)      # diag(P_C)
    k_scaled = k * jnp.exp(cum_last - cum)                # (C, D), <= 1
    state[...] = (jnp.dot(pc_diag, s_prev, precision=_EXACT)
                  + jax.lax.dot_general(k_scaled, v,
                                        (((0,), (0,)), ((), ()))))

    @pl.when(ci == nc - 1)
    def _emit():
        sT_ref[0, 0] = state[...]


def rwkv6_scan(r, k, v, w, u, state, chunk: int = 32,
               interpret: Optional[bool] = None):
    """r,k,v,w: (B,H,S,D); u: (H,D); state: (B,H,D,D) f32.
    Returns (out (B,H,S,D), new_state (B,H,D,D)).  ``interpret=None``
    picks the mode from the backend (:func:`interpret_default`)."""
    if interpret is None:
        interpret = interpret_default()
    B, H, S, D = r.shape
    C = min(chunk, S)
    assert S % C == 0, "pad S to the chunk size first"
    nc = S // C
    grid = (B, H, nc)
    kernel = functools.partial(_kernel, C=C, nc=nc)
    out, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, D), lambda b, h, c: (b, h, c, 0)),
            # u as (H, 1, D): a (1, D) block of an (H, D) array breaks
            # Mosaic's rule that the second-minor block dim divide by 8
            # or span the array
            pl.BlockSpec((1, 1, D), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r.shape, r.dtype),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, D), state.astype(jnp.float32))
    return out, s_final
