"""Pluggable wire codecs (``Compressor``) for sync payloads.

A :class:`Compressor` defines the WIRE FORMAT of a payload buffer — what the
collective actually moves — independently of the aggregation rule: the codec
compresses each worker's contribution (a lossy encode/decode round-trip in
the simulator, the literal wire arrays on hardware), then whatever
:class:`~repro.core.aggregators.Aggregator` is installed defines the mean of
the decoded contributions.  Any codec therefore composes with any
aggregator, and with either executor.

Codecs see payloads as ``(rows, ...)`` arrays with a leading worker (or
worker-shard) axis; trailing dims are flattened internally, so the same
codec handles fused :class:`~repro.comms.flat.FlatBucket` buffers and raw
leaves.  The int8 and sign codecs run the Pallas kernels in
:mod:`repro.kernels.comms` (compiled on TPU, interpret elsewhere);
``topk`` is a jnp-level sparsifier whose error-feedback residual the engine
carries in ``HSGDState.comms`` (1-bit SGD / DGC style: what compression
drops this sync is re-injected next sync, so the error stays bounded
instead of accumulating).

Registry mirrors the aggregator/executor/topology ones:
:func:`make_compressor` / :func:`register_compressor`.
"""
from __future__ import annotations

import abc
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.comms.wire import WireArray
from repro.kernels import comms as _kernels
from repro.kernels.ops import _interpret_default
from repro.obs.spans import DECODE, ENCODE


class Compressor(abc.ABC):
    """Wire codec: encode a payload to its wire arrays, decode them back.

    stateful=True codecs carry a per-worker error-feedback residual (engine
    state); for them :meth:`roundtrip` adds the residual before encoding and
    returns the new residual alongside the decoded payload.
    """

    name = "compressor"
    stateful = False
    # True when :meth:`reduce` implements the compressed-collective form —
    # the executor then hands the codec its WireOps instead of running the
    # legacy encode -> reduce_fn(decoded f32) -> decode roundtrip
    wire_reduce = False
    # True when :meth:`reduce` is elementwise-independent of payload layout
    # (no cross-element block statistics), so bucketizing the tree changes
    # nothing but memory movement.  In-array backends then elide the
    # FlatBucket pack/unpack pair from the round body entirely — this is
    # what makes the identity codec wall-clock-free under sim.
    layout_free = False

    @abc.abstractmethod
    def encode(self, x: jax.Array) -> Dict[str, jax.Array]:
        """(rows, ...) payload -> the arrays that cross the wire."""

    @abc.abstractmethod
    def decode(self, wire: Dict[str, jax.Array], like: jax.Array) -> jax.Array:
        """Wire arrays -> f32 payload shaped like ``like``."""

    @abc.abstractmethod
    def wire_spec(self, length: int, dtype) -> Tuple[WireArray, ...]:
        """Static wire arrays for ONE worker's ``length``-element payload of
        ``dtype`` — the input to :class:`~repro.comms.wire.WireStats`."""

    def roundtrip(self, x: jax.Array, residual: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """The simulator's view of the wire: what the receiver reconstructs
        from this worker's payload, plus the updated error-feedback residual
        (None for stateless codecs or when no residual is threaded)."""
        if residual is None:
            u = x
        else:
            u = x.astype(residual.dtype) + residual
        with jax.named_scope(ENCODE):
            wire = self.encode(u)
        with jax.named_scope(DECODE):
            sent = self.decode(wire, u)
        if residual is None or not self.stateful:
            return sent.astype(x.dtype), None
        return sent.astype(x.dtype), (u - sent.astype(u.dtype))

    def reduce(self, x: jax.Array, ops,
               residual: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """The compressed collective: aggregate ``x`` THROUGH the encoded
        wire form using ``ops`` (a :mod:`repro.comms.reduce` WireOps) so the
        reduction operand carries the wire dtype, not decoded f32.  Returns
        (aggregated payload, new error-feedback residual or None).  Only
        meaningful when ``wire_reduce`` is True."""
        raise NotImplementedError(
            f"{type(self).__name__} has no compressed-collective form "
            f"(wire_reduce={self.wire_reduce}); the engine falls back to "
            "the legacy roundtrip path (encode -> decode -> f32 reduce, "
            "Comms.sync)")

    def lowered_sync_ops(self, backend: str) -> Optional[int]:
        """How many counted aggregation ops ONE :meth:`reduce` call lowers
        to per payload buffer — in-array f32/i32 reduces per syncing group
        under ``"sim"``, named-axis collectives under ``"mesh"`` (the R1
        prediction).  None when no exact count exists."""
        return None

    def __repr__(self):
        return f"{type(self).__name__}()"


def _rows(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0], -1)


class IdentityCompressor(Compressor):
    """No compression — the payload crosses the wire at its own dtype.
    Useful as the FlatBucket-only configuration (fused buffers, exact
    values) and as the accounting baseline."""

    name = "identity"
    wire_reduce = True
    layout_free = True  # plain mean: bucket layout cannot change a value

    def encode(self, x):
        return {"value": x}

    def decode(self, wire, like):
        return wire["value"]

    def reduce(self, x, ops, residual=None):
        # no wire format to exploit: one group mean per buffer, with no
        # encode/decode bookkeeping around it (the identity-tax fix)
        return ops.mean(x), None

    def lowered_sync_ops(self, backend):
        return 1

    def wire_spec(self, length, dtype):
        return (WireArray("value", (length,), jnp.dtype(dtype).name),)


class Int8Compressor(Compressor):
    """Per-block symmetric int8 (block max-scale): ~4x fewer bytes than f32
    (1 byte/element + one f32 scale per ``block``)."""

    name = "int8"
    wire_reduce = True

    def __init__(self, block: int = 256):
        self.block = int(block)

    def encode(self, x):
        q, scale = _kernels.int8_quantize(
            _rows(x), block=self.block, interpret=_interpret_default())
        return {"q": q, "scale": scale}

    def decode(self, wire, like):
        y = _kernels.int8_dequantize(
            wire["q"], wire["scale"], block=self.block,
            interpret=_interpret_default())
        return y.reshape(like.shape)

    def reduce(self, x, ops, residual=None):
        """The int8 compressed allreduce: share one group-max scale per
        block (a max reduce of block stats), quantize against it, and SUM
        THE INT8 PAYLOADS in an int32 accumulator — the only elementwise
        reduction carries the widened wire dtype, exactly (|sum q| <=
        127 * members < 2^31, and < 2^24 for any plausible group, so the
        f32 decode is exact too).  One decode at the end: qsum * scale /
        count."""
        x2 = _rows(x)  # read in its own dtype: the kernel widens per tile
        r, c = x2.shape
        nb = -(-c // self.block)
        pad = nb * self.block - c
        with jax.named_scope(ENCODE):
            amax = jnp.pad(jnp.abs(x2), ((0, 0), (0, pad))) \
                .reshape(r, nb, self.block).max(axis=-1)      # (r, nb)
        scale = ops.max(amax.astype(jnp.float32)) / 127.0      # group scale
        with jax.named_scope(ENCODE):
            q = _kernels.int8_scale_quantize(
                x2, scale, block=self.block, interpret=_interpret_default())
        qsum = ops.sum(q, jnp.int32)
        count = ops.count()
        with jax.named_scope(DECODE):
            y = (jnp.pad(qsum.astype(jnp.float32), ((0, 0), (0, pad)))
                 .reshape(r, nb, self.block) * scale[..., None]) \
                .reshape(r, nb * self.block)[:, :c]
            y = y / count
            return y.reshape(x.shape).astype(x.dtype), None

    def lowered_sync_ops(self, backend):
        # mesh: pmax on the scales + psum on the int32 payload; sim: the
        # max of block stats is not a counted aggregation reduce, leaving
        # only the int32 worker-axis sum
        return 2 if backend == "mesh" else 1

    def wire_spec(self, length, dtype):
        nb = -(-length // self.block)
        return (WireArray("q", (length,), "int8"),
                WireArray("scale", (nb,), "float32"))

    def __repr__(self):
        return f"Int8Compressor(block={self.block})"


class SignCompressor(Compressor):
    """1-bit sign compression (1-bit SGD): 8 signs per uint8 plus a
    per-block ``mean|x|`` magnitude — ~32x fewer bytes than f32 at the
    default block.  Lossy by design; compose with error feedback at the
    optimizer level or accept the trajectory change (tested finite)."""

    name = "sign"
    wire_reduce = True

    def __init__(self, block: int = 1024):
        assert block % 8 == 0, block
        self.block = int(block)

    def encode(self, x):
        bits, scale = _kernels.sign_pack(
            _rows(x), block=self.block, interpret=_interpret_default())
        return {"bits": bits, "scale": scale}

    def decode(self, wire, like):
        size = _rows(like).shape[1]
        y = _kernels.sign_unpack(
            wire["bits"], wire["scale"], size=size, block=self.block,
            interpret=_interpret_default())
        return y.reshape(like.shape)

    def reduce(self, x, ops, residual=None):
        """The sign compressed reduce: the packed-uint8 payload crosses the
        wire as-is (``ops.gathered``), the receive side unpacks bits, takes
        the popcount/majority vote in int32, and scales by the group-mean
        magnitude — the aggregate ``s_bar * (#pos - #neg) / count`` per
        element.  No f32 dense payload ever hits the collective."""
        x2 = _rows(x)
        c = x2.shape[1]
        block = self.block
        with jax.named_scope(ENCODE):
            bits, scale = _kernels.sign_pack(
                x2, block=block, interpret=_interpret_default())

        @jax.named_scope(DECODE)
        def fuse(bits_g, scale_g, wmask):
            # member axis at -2 (WireOps.gathered contract)
            from repro.core.aggregators import denominator_floor
            b = bits_g.astype(jnp.int32)
            shift = jnp.arange(8, dtype=jnp.int32)
            unpacked = (b[..., None] >> shift) & 1
            unpacked = unpacked.reshape(b.shape[:-1] + (-1,))[..., :c]
            if wmask is None:
                votes = unpacked.sum(axis=-2)                  # i32 reduce
                count = float(b.shape[-2])                     # static
                ssum = scale_g.sum(axis=-2)
            else:
                votes = (unpacked * wmask.astype(jnp.int32)[..., None]) \
                    .sum(axis=-2)
                count = jnp.maximum(wmask.sum(axis=-1, keepdims=True),
                                    denominator_floor(jnp.float32))
                ssum = (scale_g * wmask[..., None]).sum(axis=-2)
            sgnsum = 2.0 * votes.astype(jnp.float32) - count   # #pos - #neg
            sbar = ssum / count                                # mean scale
            per = jnp.repeat(sbar, block, axis=-1)[..., :c]
            return per * sgnsum / count

        out = ops.gathered(fuse, bits, scale)
        return out.reshape(x.shape).astype(x.dtype), None

    def lowered_sync_ops(self, backend):
        # mesh: all_gather of bits + all_gather of scales; sim: the i32
        # vote sum + the f32 scale sum over the member axis
        return 2

    def wire_spec(self, length, dtype):
        # the kernel pads bits to whole blocks for layout, but only
        # ceil(length/8) bytes carry information — that is what crosses
        # the wire
        nb = -(-length // self.block)
        return (WireArray("bits", (-(-length // 8),), "uint8"),
                WireArray("scale", (nb,), "float32"))

    def __repr__(self):
        return f"SignCompressor(block={self.block})"


class TopKCompressor(Compressor):
    """Top-k magnitude sparsification with error feedback (Deep Gradient
    Compression): each sync ships the k = ``rate * length`` largest-|x|
    entries as (value, index) pairs; everything dropped is carried in the
    per-worker residual and re-injected at the next sync, so the
    compression error stays O(1) instead of accumulating."""

    name = "topk"
    stateful = True
    wire_reduce = True

    def __init__(self, rate: float = 1 / 16):
        assert 0 < rate <= 1, rate
        self.rate = float(rate)

    def _k(self, length: int) -> int:
        return max(1, min(length, int(round(self.rate * length))))

    def encode(self, x):
        x2 = _rows(x).astype(jnp.float32)
        k = self._k(x2.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(x2), k)
        vals = jnp.take_along_axis(x2, idx, axis=1)
        return {"values": vals, "indices": idx.astype(jnp.int32)}

    def decode(self, wire, like):
        rows = like.shape[0]
        length = _rows(like).shape[1]
        out = jnp.zeros((rows, length), jnp.float32)
        r = jnp.arange(rows)[:, None]
        out = out.at[r, wire["indices"]].set(wire["values"])
        return out.reshape(like.shape)

    def reduce(self, x, ops, residual=None):
        """The top-k compressed collective: error feedback and the sparse
        encode stay local and REPLICATE :meth:`roundtrip`'s casts exactly
        (so residual trajectories match the legacy path bitwise); the
        (values, indices) payload then rides ``ops.sparse_mean`` — a ragged
        all-gather + scatter-add decode-reduce on the mesh, the bitwise
        dense group mean under sim."""
        if residual is None:
            u = x
        else:
            u = x.astype(residual.dtype) + residual
        with jax.named_scope(ENCODE):
            wire = self.encode(u)
            # what the receivers will rebuild, for the residual
            sent = self.decode(wire, u)
        new_res = None
        if residual is not None:
            new_res = u - sent.astype(u.dtype)
        out = ops.sparse_mean(wire["values"], wire["indices"],
                              sent.astype(x.dtype))
        return out.astype(x.dtype).reshape(x.shape), new_res

    def lowered_sync_ops(self, backend):
        # mesh: all_gather of values + all_gather of indices (the
        # scatter-add decode-reduce is local); sim: one dense f32 group mean
        return 2 if backend == "mesh" else 1

    def wire_spec(self, length, dtype):
        k = self._k(length)
        return (WireArray("values", (k,), "float32"),
                WireArray("indices", (k,), "int32"))

    def __repr__(self):
        return f"TopKCompressor(rate={self.rate:g})"


# ---------------------------------------------------------------------------
# registry — the single construction path (mirrors make_aggregator et al.)
# ---------------------------------------------------------------------------
COMPRESSORS = {
    "identity": IdentityCompressor,
    "none": IdentityCompressor,
    "int8": Int8Compressor,
    "q8": Int8Compressor,
    "sign": SignCompressor,
    "1bit": SignCompressor,
    "topk": TopKCompressor,
}

CompressorLike = Union[str, Compressor, None]


def make_compressor(spec: CompressorLike = None, **kwargs) -> Compressor:
    """Resolve a compressor from an instance, a registry name, or None
    (-> IdentityCompressor, exact values at full payload bytes)."""
    if isinstance(spec, Compressor):
        if kwargs:
            raise ValueError(
                f"kwargs {sorted(kwargs)} only apply when constructing by "
                f"name; got the instance {spec!r}")
        return spec
    if spec is None:
        return IdentityCompressor(**kwargs)
    name = spec.lower()
    if name not in COMPRESSORS:
        raise KeyError(f"unknown compressor {spec!r}; "
                       f"known: {sorted(COMPRESSORS)}")
    return COMPRESSORS[name](**kwargs)


def register_compressor(name: str, cls) -> None:
    COMPRESSORS[name.lower()] = cls
