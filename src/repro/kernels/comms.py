"""Communication codecs as Pallas TPU kernels.

The payload a ``SyncEvent`` moves is a first-class design axis (signSGD,
QSGD, DGC); these kernels produce the *wire formats* the ``repro.comms``
codecs ship over the collective:

* :func:`int8_quantize` / :func:`int8_dequantize` — per-block symmetric int8
  (block max-scale): ``q = round(x * 127 / max|x_block|)``, one f32 scale per
  block.  ~4x fewer payload bytes than f32.
* :func:`sign_pack` / :func:`sign_unpack` — 1-bit sign compression: 8 signs
  packed per uint8 plus a per-block magnitude ``mean|x_block|`` (the L2-optimal
  scale for a sign vector, as in 1-bit SGD / EF-signSGD).  ~32x fewer bytes.
* :func:`int8_scale_quantize` — quantize against a caller-supplied (shared
  group-max) scale, the encode side of the int8 compressed allreduce: every
  group member's int8 payload is summable in an int32 accumulator.

All kernels tile a ``(R, C)`` payload (rows = workers or worker-shards,
columns = the flat bucket) on a (row block, column block) grid: a grid step
takes ``rb`` rows (all ``R`` when ``R <= 8``, else 8, so a step's VMEM does
not grow with the worker count) and ``tb`` codec blocks of each, with ``tb``
a multiple of 128, so the step's scales are a lane-dense ``(rb, tb)`` tile
of the ``(R, nb)`` scale array (Mosaic needs the last two block dims
divisible by 8 and 128, or whole).
Inside the step the tile is viewed as one codec block per sublane row, so
every block statistic is a lane reduction.  Arrays keep their exact shapes
(no padded copy of the bucket): the last row and column blocks may be
partial, and the kernel zeroes the columns past ``C`` itself, so block scales are computed
over real entries only — this keeps the codecs idempotent (re-encoding a
decoded payload is a fixed point), which the property suite asserts.
Payloads are read in their own dtype and widened to f32 inside the
kernel.  Like the other repo kernels they run
compiled on TPU and under ``interpret=True`` elsewhere (selected by the
:mod:`repro.kernels.ops` entry points).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_ROWS = 8              # payload rows per grid step once R exceeds it
_TILE_ELEMS = 1 << 18  # payload elements per grid step (1 MiB of f32)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tiles(r: int, c: int, block: int) -> Tuple[int, int, Tuple[int, int]]:
    """(rows per step, codec blocks per row per step, grid).  The block
    count is a multiple of 128 holding about ``_TILE_ELEMS`` elements over
    the step's rows, and no more than the row needs."""
    rb = r if r <= _ROWS else _ROWS
    nb = _cdiv(c, block)
    tb = max(1, _TILE_ELEMS // (rb * block * _LANES)) * _LANES
    tb = min(tb, _cdiv(nb, _LANES) * _LANES)
    return rb, tb, (_cdiv(r, rb), _cdiv(nb, tb))


def _spec(rb: int, width: int) -> pl.BlockSpec:
    return pl.BlockSpec((rb, width), lambda i, j: (i, j))


def _blocks(ref, c: int, block: int) -> jax.Array:
    """The (rb, tb*block) tile as (rb*tb, block) f32 codec blocks, with the
    columns past the payload's real length ``c`` zeroed."""
    r, w = ref.shape
    col = pl.program_id(1) * w + jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
    x = jnp.where(col < c, ref[...].astype(jnp.float32), 0.0)
    return x.reshape(r * (w // block), block)


def _store_scales(s_ref, col: jax.Array) -> None:
    """(rb*tb, 1) per-block column -> the lane-dense (rb, tb) scale tile,
    one transpose per payload row."""
    r, tb = s_ref.shape
    for i in range(r):
        seg = col[i * tb:(i + 1) * tb]
        s_ref[i:i + 1, :] = jnp.broadcast_to(seg, (tb, _LANES)).T[:1]


def _load_scales(s_ref) -> jax.Array:
    """Lane-dense (rb, tb) scale tile -> (rb*tb, 1) per-block column."""
    r, tb = s_ref.shape
    return jnp.concatenate(
        [jnp.broadcast_to(s_ref[i:i + 1, :], (_LANES, tb)).T[:, :1]
         for i in range(r)], axis=0)


def _lanes_to_bytes(block: int) -> jax.Array:
    """(block, block/8) 0/1 bf16 matrix: element lane i feeds byte i // 8."""
    i = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (block, block // 8), 1)
    return (i // 8 == j).astype(jnp.bfloat16)


def _bytes_to_lanes(block: int) -> jax.Array:
    """(block/8, block) 0/1 bf16 matrix: byte j feeds lanes 8j..8j+7."""
    j = jax.lax.broadcasted_iota(jnp.int32, (block // 8, block), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (block // 8, block), 1)
    return (i // 8 == j).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# int8: per-block symmetric quantization, block max-scale
# ---------------------------------------------------------------------------
def _int8_quant_kernel(x_ref, q_ref, s_ref, *, c: int, block: int):
    x = _blocks(x_ref, c, block)                            # (rb*tb, B)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)      # (rb*tb, 1)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), -127.0, 127.0)
    q_ref[...] = q.reshape(q_ref.shape).astype(jnp.int8)
    _store_scales(s_ref, scale)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def int8_quantize(x: jax.Array, *, block: int = 256,
                  interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (R, C) float -> (q int8 (R, C), scale f32 (R, ceil(C/block))).

    Zero padding never disturbs the block max, so the trailing block needs no
    special casing here (unlike :func:`sign_pack`)."""
    r, c = x.shape
    nb = _cdiv(c, block)
    rb, tb, grid = _tiles(r, c, block)
    return pl.pallas_call(
        functools.partial(_int8_quant_kernel, c=c, block=block),
        grid=grid,
        in_specs=[_spec(rb, tb * block)],
        out_specs=(_spec(rb, tb * block), _spec(rb, tb)),
        out_shape=(jax.ShapeDtypeStruct((r, c), jnp.int8),
                   jax.ShapeDtypeStruct((r, nb), jnp.float32)),
        interpret=interpret,
    )(x)


def _int8_dequant_kernel(q_ref, s_ref, y_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)
    y = q.reshape(-1, block) * _load_scales(s_ref)
    y_ref[...] = y.reshape(y_ref.shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def int8_dequantize(q: jax.Array, scale: jax.Array, *, block: int = 256,
                    interpret: bool = False) -> jax.Array:
    """(q int8 (R, C), scale f32 (R, nb)) -> x f32 (R, C)."""
    r, c = q.shape
    nb = _cdiv(c, block)
    assert scale.shape == (r, nb), (scale.shape, (r, nb))
    rb, tb, grid = _tiles(r, c, block)
    return pl.pallas_call(
        functools.partial(_int8_dequant_kernel, block=block),
        grid=grid,
        in_specs=[_spec(rb, tb * block), _spec(rb, tb)],
        out_specs=_spec(rb, tb * block),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(q, scale)


def _int8_scale_quant_kernel(x_ref, inv_ref, q_ref, *, c: int, block: int):
    x = _blocks(x_ref, c, block)                            # (rb*tb, B)
    q = jnp.clip(jnp.round(x * _load_scales(inv_ref)), -127.0, 127.0)
    q_ref[...] = q.reshape(q_ref.shape).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def int8_scale_quantize(x: jax.Array, scale: jax.Array, *, block: int = 256,
                        interpret: bool = False) -> jax.Array:
    """(x (R, C) float, scale f32 (R, ceil(C/block))) -> q int8 (R, C).

    Quantize against a CALLER-supplied per-block scale instead of the local
    block max — the compressed-allreduce form, where every group member
    quantizes against the same group-max scale so the int8 payloads are
    summable in an int32 accumulator (|sum q| <= 127 * members, exact).
    The per-block reciprocal is taken outside the kernel, once per block."""
    r, c = x.shape
    nb = _cdiv(c, block)
    assert scale.shape == (r, nb), (scale.shape, (r, nb))
    rb, tb, grid = _tiles(r, c, block)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    return pl.pallas_call(
        functools.partial(_int8_scale_quant_kernel, c=c, block=block),
        grid=grid,
        in_specs=[_spec(rb, tb * block), _spec(rb, tb)],
        out_specs=_spec(rb, tb * block),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int8),
        interpret=interpret,
    )(x, inv)


# ---------------------------------------------------------------------------
# sign: 1-bit pack into uint8, block mean-|x| magnitude
# ---------------------------------------------------------------------------
def _sign_pack_kernel(x_ref, b_ref, s_ref, *, c: int, block: int):
    x = _blocks(x_ref, c, block)                            # (rb*tb, B)
    nb = _cdiv(c, block)
    tb = s_ref.shape[1]
    blk = pl.program_id(1) * tb + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], 1), 0) % tb
    count = jnp.where(blk == nb - 1, float(c - (nb - 1) * block),
                      float(block))
    _store_scales(s_ref, jnp.sum(jnp.abs(x), axis=-1, keepdims=True) / count)
    # bit k of byte j is x[8j+k] >= 0: weight each sign by 2^k and sum each
    # run of 8 lanes into its byte on the MXU (exact: every partial sum is
    # an integer <= 255)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    weight = jnp.left_shift(1, lane % 8).astype(jnp.float32)
    w = jnp.where(x >= 0, weight, 0.0).astype(jnp.bfloat16)
    packed = jnp.dot(w, _lanes_to_bytes(block),
                     preferred_element_type=jnp.float32)    # (rb*tb, B/8)
    b_ref[...] = packed.reshape(b_ref.shape).astype(jnp.int32) \
        .astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sign_pack(x: jax.Array, *, block: int = 1024,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (R, C) float -> (bits uint8 (R, ceil(C/block)*block/8),
    scale f32 (R, ceil(C/block))).

    Bit k of byte j in a block is ``x[8j+k] >= 0``; the block scale is
    ``mean|x|`` over the block's *real* entries (the padded tail is excluded
    via the per-block denominator), so a re-encoded payload keeps its scale."""
    assert block % 8 == 0, block
    r, c = x.shape
    nb = _cdiv(c, block)
    rb, tb, grid = _tiles(r, c, block)
    return pl.pallas_call(
        functools.partial(_sign_pack_kernel, c=c, block=block),
        grid=grid,
        in_specs=[_spec(rb, tb * block)],
        out_specs=(_spec(rb, tb * block // 8), _spec(rb, tb)),
        out_shape=(jax.ShapeDtypeStruct((r, nb * block // 8), jnp.uint8),
                   jax.ShapeDtypeStruct((r, nb), jnp.float32)),
        interpret=interpret,
    )(x)


def _sign_unpack_kernel(b_ref, s_ref, y_ref, *, block: int):
    packed = b_ref[...].astype(jnp.int32).reshape(-1, block // 8)
    # spread byte j over lanes 8j..8j+7 on the MXU (exact: bytes <= 255)
    spread = jnp.dot(packed.astype(jnp.bfloat16), _bytes_to_lanes(block),
                     preferred_element_type=jnp.float32)    # (rb*tb, B)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    bits = jnp.right_shift(spread.astype(jnp.int32), lane % 8) & 1
    sgn = bits.astype(jnp.float32) * 2.0 - 1.0
    y_ref[...] = (sgn * _load_scales(s_ref)).reshape(y_ref.shape)


@functools.partial(jax.jit, static_argnames=("size", "block", "interpret"))
def sign_unpack(bits: jax.Array, scale: jax.Array, *, size: int,
                block: int = 1024, interpret: bool = False) -> jax.Array:
    """(bits uint8 (R, nb*block/8), scale f32 (R, nb)) -> x f32 (R, size):
    ``+scale`` where the bit is set, ``-scale`` where clear."""
    assert block % 8 == 0, block
    r = bits.shape[0]
    nb = _cdiv(size, block)
    assert bits.shape == (r, nb * block // 8), (bits.shape, (r, nb * block // 8))
    assert scale.shape == (r, nb), (scale.shape, (r, nb))
    rb, tb, grid = _tiles(r, size, block)
    return pl.pallas_call(
        functools.partial(_sign_unpack_kernel, block=block),
        grid=grid,
        in_specs=[_spec(rb, tb * block // 8), _spec(rb, tb)],
        out_specs=_spec(rb, tb * block),
        out_shape=jax.ShapeDtypeStruct((r, size), jnp.float32),
        interpret=interpret,
    )(bits, scale)
