"""Reduce a profiler trace to the device time under each of the program's
named scopes, and the device's idle time under each of ``HSGD.run_rounds``'s
host spans.

The round programs name their work with ``jax.named_scope``, and each
device operation's metadata in the ``.xplane.pb`` carries the scope path it
was traced under as its ``tf_op`` stat, for example
``jit(<lambda>)/while/body/closed_call/vmap(transpose(jvp(local/fwd)))/
dot_general:``.  ``jax.profiler.ProfileData`` does not expose an event's
metadata, so :func:`read_events` decodes the file with ``google.protobuf``
from a descriptor of the few XSpace fields it needs.  An operation that XLA
adds after tracing has no ``tf_op`` (layout copies, asynchronous copies,
the ``reduce-window`` rewrites of a cumulative sum); :func:`read_events`
gives it the path of the instruction that calls its computation, from
the optimized program (``Hlo Proto``) that the trace's ``/host:metadata``
plane holds, and that instruction's caller where it has none: a copy in a
loop body is the loop's.

The reduction keeps ``trace_reduce``'s rules: the window runs from the
first ``chipbench.*`` span's start to the last one's end, a device's busy
time is the union of its ``XLA Ops`` intervals inside the window, and
control flow (``while``, ``conditional``, ``call``) holds other operations
and is not counted as an operation.  Each other operation falls under the
first scope of :func:`scope_of` that its path matches, else it is
``unscoped``; ``control`` is busy time in which only control flow ran.  So
per device the scopes, ``unscoped`` and ``control`` add up to the busy
time, as long as no two operations of a device's ``XLA Ops`` line
overlap.

The scope names are the program's own (``repro.obs.spans``); a program
without that module has no scopes, and every operation is ``unscoped``.
The names live in the debug info of the lowered program, which JAX leaves
out of a compile cache key unless ``jax_compilation_cache_include_
metadata_in_key`` is on: a program loaded from a cache filled before the
scopes existed carries none.  :func:`per_step` refuses a reduction with no
time under ``local``, which is what either case gives.

Each idle interval of a device inside the window is split among the
``hsgd.*`` host spans by the innermost span covering each part, and given
whole to the span that holds most of it (``outside hsgd`` where none
does): that says what the program's host code was doing while the device
waited.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench.trace_reduce import (CONTROL_FLOW, OPS_LINE, SPAN_PREFIX,
                                    _clip, _is_device, op_name, union)

HOST_PREFIX = "hsgd."
CALL_SPAN = "hsgd.run_rounds"
OUTSIDE = "outside hsgd"
UNSCOPED, CONTROL = "unscoped", "control"

# (plane, line, name, start_ns, duration_ns, meta): ``meta`` is the tf_op
# path of a device operation, or the stats of a host event
Event = Tuple[str, str, str, float, float, object]


@functools.cache
def _rules():
    """The program's scope names, and patterns built from them: the local
    block, one sync with its part, and the probe (None without names)."""
    try:
        from repro.obs import spans as n
    except ImportError:   # a program that names no scopes
        return None
    esc = re.escape
    level = esc(n.SYNC_LEVEL).replace(esc("{level}"), r"\d+")
    part = "|".join(esc(x) for x in (n.ENCODE, n.COLLECTIVE, n.DECODE))
    return (n,
            re.compile(rf"(?:^|/){esc(n.LOCAL)}/"),
            re.compile(rf"{esc(n.SYNC_FOLD)}|{level}(?:/(?:{part}))?"),
            re.compile(rf"(?:^|[/(]){esc(n.PROBE)}(?:[/)]|$)"))


def scope_of(tf_op: str) -> str:
    """The scope a device operation's ``tf_op`` path falls under.

    The gradient carries the loss's ``local/fwd`` under ``transpose(...)``;
    what ``jax.checkpoint`` recomputes there sits under
    ``rematted_computation`` and is the forward again (``local/remat``).
    The rest of the local block is ``local``: the scan's own bookkeeping,
    and the operations XLA's loop passes rebuild with the loop's name
    (``jit(<lambda>)/local/while:``), such as the bfloat16 casts of the
    float32 weights.  A sync is ``sync/L<level>`` with its ``encode``,
    ``collective`` or ``decode`` part where it has one, or ``sync/fold``,
    which holds the syncs it posts.  A path under none of these, or no
    path, is ``unscoped``."""
    rules = _rules()
    if rules is None:
        return UNSCOPED
    n, local, sync, probe = rules
    path = tf_op.split(";", 1)[0]
    if n.LOCAL_FWD in path:
        if "rematted_computation" in path:
            return f"{n.LOCAL}/remat"
        return f"{n.LOCAL}/bwd" if "transpose(" in path else n.LOCAL_FWD
    if n.LOCAL_UPDATE in path:
        return n.LOCAL_UPDATE
    if local.search(path):
        return n.LOCAL
    m = sync.search(path)
    if m:
        return m.group(0)
    if probe.search(path):
        return n.PROBE
    return UNSCOPED


class NoScopes(ValueError):
    """A trace whose device time carries none of the program's scopes."""


@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    busy_s: float                  # mean over devices
    scope_s: Dict[str, float]      # mean over devices, with unscoped/control
    host_gap_s: Dict[str, float]   # idle seconds by host span, device mean
    calls: int                     # ``hsgd.run_rounds`` spans in the window
    unscoped_ops: List[Tuple[str, float]]  # the largest, (op, seconds)
    path_s: Dict[str, float]       # by ``tf_op`` path, device mean


def reduce_scopes(events, top: int = 10) -> Optional[ScopeSummary]:
    """Reduce :func:`read_events` output; None when no device operation
    was recorded (a host-only backend)."""
    harness, host, ops = [], [], defaultdict(list)
    for plane, line, name, start, dur, meta in events:
        if _is_device(plane):
            if line == OPS_LINE:
                ops[plane].append((name, start, start + dur, meta))
        elif name.startswith(SPAN_PREFIX):
            harness.append((start, start + dur))
        elif name.startswith(HOST_PREFIX):
            host.append((name, start, start + dur))
    if not ops:
        return None
    if harness:
        t0, t1 = min(s for s, _ in harness), max(e for _, e in harness)
    else:
        t0 = min(s for evs in ops.values() for _, s, _, _ in evs)
        t1 = max(e for evs in ops.values() for _, _, e, _ in evs)
    n = len(ops)
    scope_s, gap_s, unscoped, path_s = (defaultdict(float), defaultdict(float),
                                        defaultdict(float), defaultdict(float))
    busy = 0.0
    for evs in ops.values():
        merged = _clip(union((s, e) for _, s, e, _ in evs), t0, t1)
        dev_busy = sum(e - s for s, e in merged)
        busy += dev_busy
        leaves = [(max(s, t0), min(e, t1), op_name(name), meta)
                  for name, s, e, meta in evs if e > t0 and s < t1]
        leaves = [lf for lf in leaves if not lf[2].startswith(CONTROL_FLOW)]
        for s, e, name, meta in leaves:
            scope = scope_of(meta or "")
            scope_s[scope] += (e - s) * 1e-9 / n
            path_s[meta or ""] += (e - s) * 1e-9 / n
            if scope == UNSCOPED:
                unscoped[f"{name} | {meta}"] += (e - s) * 1e-9 / n
        leaf_busy = sum(e - s for s, e in union((s, e)
                                                for s, e, _, _ in leaves))
        scope_s[CONTROL] += (dev_busy - leaf_busy) * 1e-9 / n
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gap_s[host_span(host, gs, ge)] += (ge - gs) * 1e-9 / n
    scope_s.setdefault(UNSCOPED, 0.0)
    return ScopeSummary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy * 1e-9 / n,
        scope_s=dict(scope_s), host_gap_s=dict(gap_s),
        calls=sum(1 for name, s, e in host
                  if name == CALL_SPAN and s < t1 and e > t0),
        unscoped_ops=sorted(unscoped.items(), key=lambda kv: -kv[1])[:top],
        path_s=dict(path_s))


def under(summary: Optional[ScopeSummary],
          name: str) -> Optional[Dict[str, float]]:
    """Device seconds (mean over devices) of the operations whose ``tf_op``
    path holds the named scope ``name`` (``jax.named_scope(name)`` anywhere
    in the program, such as a layer's inside the model), split as
    :func:`scope_of` splits the loss: ``fwd``, ``bwd`` (under
    ``transpose(...)``) and ``remat`` (what ``jax.checkpoint`` recomputes
    there).  None where nothing was recorded; zeros where no operation
    holds the scope."""
    if summary is None:
        return None
    held = re.compile(rf"(?:^|[/(]){re.escape(name)}(?:[/)]|$)")
    out = {"fwd": 0.0, "bwd": 0.0, "remat": 0.0}
    for tf_op, sec in summary.path_s.items():
        path = tf_op.split(";", 1)[0].rstrip(":")
        if held.search(path):
            part = ("remat" if "rematted_computation" in path
                    else "bwd" if "transpose(" in path else "fwd")
            out[part] += sec
    return out


def host_span(spans, gs: float, ge: float) -> str:
    """The ``hsgd.*`` span that is innermost over most of ``[gs, ge]``.
    Spans of one thread nest, so the innermost span covering an instant is
    the one that started last."""
    cover = [(s, e, name) for name, s, e in spans if s < ge and e > gs]
    if not cover:
        return OUTSIDE
    cuts = sorted({gs, ge} | {t for s, e, _ in cover for t in (s, e)
                              if gs < t < ge})
    held = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        inside = [(s, name) for s, e, name in cover if s <= a and e >= b]
        held[max(inside)[1] if inside else OUTSIDE] += b - a
    return max(held.items(), key=lambda kv: kv[1])[0]


def per_step(summary: ScopeSummary, steps: int) -> Dict[str, float]:
    """The local step's and the syncs' device milliseconds per local step
    of ``steps`` traced steps, and the device's idle milliseconds per call
    while the host is inside ``hsgd.run_rounds``.  Raises
    :class:`NoScopes` where no busy time fell under ``local``."""
    s, rules = summary.scope_s, _rules()
    n = rules[0] if rules else None
    if n is None or summary.busy_s > 0 and not any(
            v > 0 for k, v in s.items() if k.split("/")[0] == n.LOCAL):
        raise NoScopes(
            "no device time under a local scope: the program names no "
            "scopes, or its round programs came from a compile cache filled "
            "before they did (JAX keys the cache without debug info, where "
            "the scopes live, unless jax_compilation_cache_include_metadata_"
            "in_key is on)")
    ms = lambda v: 1e3 * v / steps
    return {
        "local.fwd_ms": ms(s.get(n.LOCAL_FWD, 0.0)
                           + s.get(f"{n.LOCAL}/remat", 0.0)),
        "local.bwd_ms": ms(s.get(f"{n.LOCAL}/bwd", 0.0)),
        "local.update_ms": ms(s.get(n.LOCAL_UPDATE, 0.0)),
        "local.other_ms": ms(s.get(n.LOCAL, 0.0)),
        "sync.ms": ms(sum(v for k, v in s.items() if rules[2].fullmatch(k))),
        "plan.host_gap_ms": 1e3 * sum(
            v for k, v in summary.host_gap_s.items() if k != OUTSIDE)
        / max(summary.calls, 1),
    }


def step_reading(ctx, key: str) -> Optional[float]:
    """``per_step``'s ``key`` for a per-layer reader's context: None where
    the run recorded no device trace, or a trace without the program's
    scopes (a parent that names none, or one whose programs came from a
    cache filled without them)."""
    if ctx.scopes is None:
        return None
    try:
        return per_step(ctx.scopes, ctx.traced_steps)[key]
    except NoScopes:
        return None


# -- the file ---------------------------------------------------------------
_FIELDS = {   # message -> [(number, name, type)], "*" marking a repeated one
    "XStat": [(1, "metadata_id", "int64"), (2, "double_value", "double"),
              (3, "uint64_value", "uint64"), (4, "int64_value", "int64"),
              (5, "str_value", "string"), (6, "bytes_value", "bytes"),
              (7, "ref_value", "uint64")],
    "XEvent": [(1, "metadata_id", "int64"), (2, "offset_ps", "int64"),
               (3, "duration_ps", "int64"), (4, "stats", "*XStat")],
    "XLine": [(2, "name", "string"), (3, "timestamp_ns", "int64"),
              (4, "events", "*XEvent")],
    "XEventMetadata": [(1, "id", "int64"), (2, "name", "string"),
                       (4, "display_name", "string"),
                       (5, "stats", "*XStat")],
    "XStatMetadata": [(1, "id", "int64"), (2, "name", "string")],
    "EventMetadataEntry": [(1, "key", "int64"),
                           (2, "value", "XEventMetadata")],
    "StatMetadataEntry": [(1, "key", "int64"), (2, "value", "XStatMetadata")],
    "XPlane": [(2, "name", "string"), (3, "lines", "*XLine"),
               (4, "event_metadata", "*EventMetadataEntry"),
               (5, "stat_metadata", "*StatMetadataEntry")],
    "XSpace": [(1, "planes", "*XPlane")],
    # xla.HloProto, the optimized program of /host:metadata's ``Hlo Proto``
    "OpMetadata": [(2, "op_name", "string")],
    "HloInstructionProto": [(1, "name", "string"),
                            (7, "metadata", "OpMetadata"),
                            (38, "called_computation_ids", "*int64")],
    "HloComputationProto": [(2, "instructions", "*HloInstructionProto"),
                            (5, "id", "int64")],
    "HloModuleProto": [(3, "computations", "*HloComputationProto")],
    "HloProto": [(1, "hlo_module", "HloModuleProto")],
}


def xspace_class():
    """The message class of an ``.xplane.pb`` file (``tsl.profiler.XSpace``,
    restricted to the fields read here; the rest parse as unknown)."""
    return message_class("XSpace")


@functools.cache
def message_class(message: str):
    """The class of ``message``, one of :data:`_FIELDS`."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
              "bytes": F.TYPE_BYTES}
    pkg = "chipbench_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for number, name, kind in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if kind[0] == "*"
                            else F.LABEL_OPTIONAL)
            if name.endswith("_value"):   # XStat's ``oneof value``
                if not m.oneof_decl:
                    m.oneof_decl.add(name="value")
                f.oneof_index = 0
            kind = kind.lstrip("*")
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.{message}"))


def _stat_value(stat, names):
    field = stat.WhichOneof("value")
    if field == "ref_value":
        return names.get(stat.ref_value, "")
    return None if field is None else getattr(stat, field)


def held_paths(space) -> Dict[Tuple[int, str], str]:
    """``(program id, instruction name) -> path`` for each instruction of
    the programs in ``space``'s ``/host:metadata`` that has no path of its
    own but sits in a computation whose caller, or whose caller's caller,
    has one: the nearest such path."""
    hlo, out = message_class("HloProto"), {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            program = re.search(r"\((\d+)\)$", entry.value.name)
            proto = next((s.bytes_value for s in entry.value.stats
                          if names.get(s.metadata_id) == "Hlo Proto"), None)
            if program is None or proto is None:
                continue
            comps = hlo.FromString(proto).hlo_module.computations
            callers = defaultdict(list)   # computation -> (path, caller's)
            for comp in comps:
                for ins in comp.instructions:
                    for cid in ins.called_computation_ids:
                        callers[cid].append((ins.metadata.op_name, comp.id))
            held: Dict[int, str] = {}

            def path_of(cid: int, seen=()) -> str:
                if cid not in held:
                    own = [p for p, _ in callers[cid] if p]
                    held[cid] = own[0] if own else next(
                        (p for p in (path_of(up, seen + (cid,))
                                     for _, up in callers[cid]
                                     if up not in seen) if p), "")
                return held[cid]

            for comp in comps:
                for ins in comp.instructions:
                    if not ins.metadata.op_name and path_of(comp.id):
                        out[(int(program.group(1)), ins.name)] = \
                            path_of(comp.id)
    return out


def read_events(path: str) -> List[Event]:
    """Flat events of one ``.xplane.pb``, timed as ``trace_reduce.
    profile_events`` times them; a device operation's ``meta`` is its
    metadata's ``tf_op``, else its :func:`held_paths` path, else ''; a
    host event's ``meta`` is its stats."""
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    held = held_paths(space)
    out: List[Event] = []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        device = _is_device(plane.name)
        meta = {}
        for entry in plane.event_metadata:
            md = entry.value
            if device:
                stats = {names.get(s.metadata_id): _stat_value(s, names)
                         for s in md.stats}
                meta[entry.key] = (md.name, stats.get("tf_op") or held.get(
                    (stats.get("program_id"), md.display_name), ""))
            else:
                meta[entry.key] = (md.name, None)
        for line in plane.lines:
            for ev in line.events:
                name, tf_op = meta.get(ev.metadata_id, ("", ""))
                info = tf_op if device else {
                    names.get(s.metadata_id, ""): _stat_value(s, names)
                    for s in ev.stats}
                out.append((plane.name, line.name, name,
                            line.timestamp_ns + ev.offset_ps // 1000,
                            ev.duration_ps // 1000, info))
    return out
