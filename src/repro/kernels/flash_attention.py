"""Flash attention Pallas kernel (TPU target, interpret-validated on CPU).

Blockwise online-softmax with explicit BlockSpec VMEM tiling:
  grid = (B, Hq, S/bq, S/bk); the kv dimension is innermost, so the f32
  scratch accumulators (acc, row-max m, row-sum l) persist across kv blocks
  of one q block (TPU grid iteration is sequential).  Causal and
  sliding-window masks are applied from block-local iotas; GQA maps query
  head -> kv head in the BlockSpec index_map, so no KV replication is ever
  materialized.  Tile sizes default to 128x128 — MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            scale: float):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    s = q @ k.T                                          # (bq, bk)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B,Hq,S,D); k,v: (B,Hkv,S,D) -> (B,Hq,S,D).  ``interpret=None``
    picks the mode from the backend (:func:`interpret_default`)."""
    if interpret is None:
        interpret = interpret_default()
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, "pad S to the block size first"
    nq, nk = S // bq, S // bk
    grid = (B, Hq, nq, nk)
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                               window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
