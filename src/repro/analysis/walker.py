"""The jaxpr walker: structured extraction of what a lowered program ships.

Every perf claim this repo makes about its lowered round programs —
O(dtypes) fused collectives (comms), named-axis lowering per sync level
(mesh), no host round-trips inside the scanned body — used to be verified
by counting substrings of ``str(jax.make_jaxpr(...))`` in individual tests.
This module is the one real implementation those assertions now share: it
recursively walks a (closed) jaxpr — descending into every sub-jaxpr a
primitive carries (``pjit``, ``scan``, ``shard_map``, ``cond`` branches,
``custom_jvp``/``vjp`` calls, ...) — and records the operations that matter
for sync-plan auditing:

* **collectives** — ``psum`` (and its ``check_vma`` rewrite
  ``psum_invariant``),
  ``pmean``\\*, ``all_gather``, ``all_to_all``, ``ppermute``, ... with their
  named axes, operand dtypes, element counts and bytes.  These ARE the wire
  under the mesh executor.  (\\*``lax.pmean`` lowers to psum + div, so it is
  counted through its psum; ``pbroadcast`` is replication bookkeeping, not
  traffic, and is deliberately excluded.)
* **reduces** — ``reduce_sum`` / ``dot_general``: the in-array reshape-mean
  and membership segment-mean forms the sim executor aggregates with.
* **callbacks** / **transfers** — ``debug_callback``, ``debug_print``,
  ``pure_callback``, ``io_callback``, ``device_put``, in/outfeed: host
  round-trips that must
  never appear inside a compiled round body (rule R3).

The result is plain data (:class:`JaxprSummary` of :class:`OpRecord`), so
the rule engine in :mod:`repro.analysis.rules` and all its tests operate on
values, never on live tracers.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import Counter
from typing import Any, Dict, Iterable, Tuple

import jax
import numpy as np

try:  # jax >= 0.4.33: the public IR types
    from jax.extend.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover — older jax
    from jax.core import ClosedJaxpr, Jaxpr

# psum_invariant/pbroadcast are what check_vma=True shard_map rewrites psum
# into (psum2 on older JAX).
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "psum_invariant", "pmean", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "psum_scatter",
    "reduce_scatter",
})
# jax.debug.print binds its own debug_print primitive on JAX >= 0.9
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
})
TRANSFER_PRIMS = frozenset({"device_put", "infeed", "outfeed"})
REDUCE_PRIMS = frozenset({"reduce_sum", "dot_general"})


@dataclasses.dataclass(frozen=True)
class OpRecord:
    """One audited equation: where it sits and what it consumes."""
    primitive: str
    path: str                    # "/"-joined enclosing primitives ("" = top)
    axes: Tuple[str, ...]        # named mesh axes (collectives only)
    dtypes: Tuple[str, ...]      # operand dtypes
    elements: int                # total operand elements
    nbytes: int                  # total operand bytes

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OpRecord":
        return cls(d["primitive"], d["path"], tuple(d["axes"]),
                   tuple(d["dtypes"]), int(d["elements"]), int(d["nbytes"]))


@dataclasses.dataclass(frozen=True)
class JaxprSummary:
    """Everything the walker saw, as plain data."""
    counts: Dict[str, int]             # primitive name -> eqn count
    collectives: Tuple[OpRecord, ...]
    callbacks: Tuple[OpRecord, ...]
    transfers: Tuple[OpRecord, ...]
    reduces: Tuple[OpRecord, ...]

    def count(self, *prims: str) -> int:
        """Total eqn count over the given primitive names."""
        return sum(self.counts.get(p, 0) for p in prims)

    @property
    def collective_count(self) -> int:
        return len(self.collectives)


def _subjaxprs(params: Dict[str, Any]) -> Iterable[Any]:
    for v in params.values():
        if isinstance(v, (Jaxpr, ClosedJaxpr)):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, (Jaxpr, ClosedJaxpr)):
                    yield x


def _operand_stats(eqn) -> Tuple[Tuple[str, ...], int, int]:
    dtypes, elements, nbytes = [], 0, 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            continue
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        dt = np.dtype(aval.dtype)
        dtypes.append(dt.name)
        elements += n
        nbytes += n * dt.itemsize
    return tuple(dtypes), elements, nbytes


def _axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(axes, str):
        axes = (axes,)
    # named mesh axes only — reduce_sum reuses the 'axes' param for
    # positional ints, which are not wire-relevant
    return tuple(a for a in axes if isinstance(a, str))


def walk(jaxpr) -> JaxprSummary:
    """Walk a (Closed)Jaxpr and every nested sub-jaxpr; return the summary."""
    counts: Counter = Counter()
    collectives, callbacks, transfers, reduces = [], [], [], []

    def visit(j, path: str) -> None:
        j = getattr(j, "jaxpr", j)  # ClosedJaxpr -> Jaxpr
        for eqn in j.eqns:
            name = eqn.primitive.name
            counts[name] += 1
            bucket = (collectives if name in COLLECTIVE_PRIMS else
                      callbacks if name in CALLBACK_PRIMS else
                      transfers if name in TRANSFER_PRIMS else
                      reduces if name in REDUCE_PRIMS else None)
            if bucket is not None:
                dtypes, elements, nbytes = _operand_stats(eqn)
                bucket.append(OpRecord(name, path, _axes(eqn), dtypes,
                                       elements, nbytes))
            sub_path = f"{path}/{name}" if path else name
            for sub in _subjaxprs(eqn.params):
                visit(sub, sub_path)

    visit(jaxpr, "")
    return JaxprSummary(dict(counts), tuple(collectives), tuple(callbacks),
                        tuple(transfers), tuple(reduces))


def trace(fn, *args, **kwargs) -> JaxprSummary:
    """``walk(jax.make_jaxpr(fn)(*args))`` — the one-liner the migrated
    test assertions use."""
    return walk(jax.make_jaxpr(fn)(*args, **kwargs))


_ADDR = None  # compiled lazily; "at 0x7f..." object addresses in the print


def fingerprint(jaxpr) -> str:
    """Stable digest of a traced program: two fingerprints are equal iff
    the lowered programs are equation-for-equation identical — the
    'jaxpr-identical' claim tests assert without shipping the whole string
    around.  Object addresses in the pretty-print (``custom_jvp_call``'s
    ``jvp_jaxpr_thunk=<function ... at 0x...>``) differ between otherwise
    identical traces and are scrubbed."""
    global _ADDR
    if _ADDR is None:
        import re
        _ADDR = re.compile(r"0x[0-9a-f]+")
    text = _ADDR.sub("0x", str(jaxpr))
    return hashlib.sha256(text.encode("utf-8")).hexdigest()[:16]
