"""Public kernel entry points.

Auto-select ``interpret=True`` off-TPU so the same call sites work in CPU
tests (interpret mode executes the kernel body in Python) and compile to real
Mosaic kernels on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import comms as _comms
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.ssd_scan import ssd_scan as _ssd


@functools.cache
def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    # interpret mode is slow; shrink blocks so CPU tests stay fast
    if interpret:
        block_q = min(block_q, 32)
        block_k = min(block_k, 32)
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        chunk = min(chunk, 16)
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def rglru_scan(a, b, *, block: int = 128, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        block = min(block, 32)
    return _rglru(a, b, block=block, interpret=interpret)


# -- communication codecs ----------------------------------------------------
# NOTE: the block size is part of a codec's *numerics* (scales are per
# block), so these entry points shrink it in interpret mode like the other
# kernels — fast CPU tests, consistent within a platform — while the
# ``repro.comms`` compressors pin their configured block explicitly via
# ``repro.kernels.comms`` so a codec's wire format never depends on where
# it traced.
def _comm_block(block: int, interpret: bool) -> int:
    return min(block, 64) if interpret else block


def int8_quantize(x, *, block: int = 256, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    return _comms.int8_quantize(x, block=_comm_block(block, interpret),
                                interpret=interpret)


def int8_dequantize(q, scale, *, block: int = 256,
                    interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    return _comms.int8_dequantize(q, scale,
                                  block=_comm_block(block, interpret),
                                  interpret=interpret)


def int8_scale_quantize(x, scale, *, block: int = 256,
                        interpret: Optional[bool] = None):
    """Quantize against a caller-supplied per-block scale.  The ``block``
    here is pinned by the scale's shape (one scale per block), so unlike the
    other comms entry points it is NOT shrunk in interpret mode — the caller
    already committed to a blocking when it computed the scales."""
    if interpret is None:
        interpret = _interpret_default()
    return _comms.int8_scale_quantize(x, scale, block=block,
                                      interpret=interpret)


def sign_pack(x, *, block: int = 1024, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    return _comms.sign_pack(x, block=_comm_block(block, interpret),
                            interpret=interpret)


def sign_unpack(bits, scale, *, size: int, block: int = 1024,
                interpret: Optional[bool] = None):
    if interpret is None:
        interpret = _interpret_default()
    return _comms.sign_unpack(bits, scale, size=size,
                              block=_comm_block(block, interpret),
                              interpret=interpret)
