"""Model FLOPs per trained token, the convention every family's
``flops_per_token(m, seq)`` follows.

A family counts the multiply-adds of the forward pass for one token, with
nothing recomputed: every weight matrix, the head included (the embedding
lookup is free), and its mixers' sequence terms.  Elementwise work (norms,
activations, softmax, decays) is not counted.  Training takes the forward
and a backward of twice its work, two operations per multiply-add.
"""
from __future__ import annotations


def training_flops(macs: int) -> float:
    """Training FLOPs of a token whose forward pass takes ``macs``
    multiply-adds."""
    return 3 * 2 * float(macs)
