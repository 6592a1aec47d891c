"""Bytes the batched pricing kernel must move per call, from the shapes
of its operands and results, and a spy that records them.

The kernel reads every operand in full except the pool's node table
(``s_node``, the last operand), which it gathers only at the chosen
positions: that operand counts as many elements as the positions
result (``pos``, the sixth) holds.  Results are written once.  The
kernel computes in int32 only, so these bytes, not operations, bound
it on the chip.
"""
from __future__ import annotations

from typing import List

POS_RESULT = 5


def pricing_kernel_bytes(args, outs) -> int:
    *full, s_node = args
    gathered = min(s_node.nbytes,
                   outs[POS_RESULT].size * s_node.dtype.itemsize)
    return (sum(a.nbytes for a in full) + gathered
            + sum(o.nbytes for o in outs))


class PricingSpy:
    """Wraps ``batch_solver._get_kernel`` so each pricing-kernel call's
    bytes are recorded while ``on`` is set."""

    def __init__(self):
        self.on = False
        self.bytes: List[int] = []
        self._mod = self._orig = None

    def install(self) -> bool:
        from repro.core import batch_solver as bs
        get = getattr(bs, "_get_kernel", None)
        if get is None:
            return False
        self._mod, self._orig = bs, get

        def wrapped(*a):
            kern = get(*a)

            def call(*args):
                out = kern(*args)
                if self.on:
                    self.bytes.append(pricing_kernel_bytes(args, out))
                return out
            return call
        bs._get_kernel = wrapped
        return True

    def remove(self) -> None:
        if self._mod is not None:
            self._mod._get_kernel = self._orig
            self._mod = None
