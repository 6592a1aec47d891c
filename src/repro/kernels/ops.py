"""Jit'd dispatch wrappers over the Pallas kernels.

Model code calls these with model-layout tensors; the wrappers transpose
to kernel layout, pad to tile multiples, and dispatch to the Pallas
implementation, which picks Pallas interpret mode from the backend at
call time (compiled on a TPU, interpreted elsewhere).
``impl="xla"`` falls through to the jnp oracle (the default inside models,
since XLA fuses those fine and the dry-run needs no Pallas lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _rwkv
from repro.kernels import rmsnorm as _rms


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    impl: str = "pallas"):
    """Model layout q:(B,S,Hq,Dh), k/v:(B,S,Hkv,Dh) -> (B,S,Hq,Dh)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if impl == "xla":
        out = ref.flash_attention_ref(qt, kt, vt, causal, window)
    else:
        S = qt.shape[2]
        bq = bk = 128
        pad = (-S) % bq
        if pad:
            zq = jnp.zeros(qt.shape[:2] + (pad, qt.shape[3]), qt.dtype)
            zk = jnp.zeros(kt.shape[:2] + (pad, kt.shape[3]), kt.dtype)
            qt = jnp.concatenate([qt, zq], axis=2)
            kt = jnp.concatenate([kt, zk], axis=2)
            vt = jnp.concatenate([vt, zk], axis=2)
        out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                                  block_q=bq, block_k=bk)
        if pad:
            out = out[:, :, :S]
    return jnp.swapaxes(out, 1, 2)


def rwkv6_scan(r, k, v, w, u, state, impl: str = "pallas", chunk: int = 32):
    """Model layout r/k/v/w:(B,S,H,Dh), u:(H,Dh), state:(B,H,Dh,Dh).
    Returns (out (B,S,H,Dh), new_state)."""
    rt, kt, vt, wt = (jnp.swapaxes(t, 1, 2) for t in (r, k, v, w))
    if impl == "xla":
        out, s = ref.rwkv6_scan_ref(rt, kt, vt, wt, u, state)
    else:
        S = rt.shape[2]
        pad = (-S) % chunk
        if pad:
            def zpad(t, fill=0.0):
                z = jnp.full(t.shape[:2] + (pad, t.shape[3]), fill, t.dtype)
                return jnp.concatenate([t, z], axis=2)
            rt, kt, vt = zpad(rt), zpad(kt), zpad(vt)
            wt = zpad(wt, 1.0)   # decay 1 = no-op steps
        out, s = _rwkv.rwkv6_scan(rt, kt, vt, wt, u, state, chunk=chunk)
        if pad:
            out = out[:, :, :S]
    return jnp.swapaxes(out, 1, 2), s


def rmsnorm(x, scale, eps: float = 1e-5, impl: str = "pallas"):
    if impl == "xla":
        return ref.rmsnorm_ref(x, scale, eps)
    return _rms.rmsnorm(x, scale, eps)
