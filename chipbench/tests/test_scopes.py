"""The reduction by the program's named scopes: the rule on ``tf_op`` paths,
busy time split into scopes, idle time by host span, and the decoder, on
hand-made events and on recorded TPU traces (``testdata/tiny.xplane.pb``
from ``record_trace.py``, ``testdata/scoped.xplane.pb`` from
``record_scoped_trace.py``)."""
from pathlib import Path

import pytest

from chipbench.scopes import (NoScopes, host_span, per_step, read_events,
                              reduce_scopes, scope_of, under)
from chipbench.trace_reduce import profile_events, reduce_events

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"
TESTDATA = Path(__file__).resolve().parents[1] / "testdata"

# paths copied from the round programs' operations in a TPU v5e trace
# (``testdata/scoped.xplane.pb``; REMAT's path from a mamba2 round)
BODY = "jit(<lambda>)/local/while/body/closed_call/"
FWD = BODY + "vmap(jvp(local/fwd))/gather:"
BWD = BODY + "vmap(transpose(jvp(local/fwd)))/scatter-add:"
REMAT = (BODY + "vmap(transpose(jvp(local/fwd)))/while/body/closed_call/"
         "checkpoint/rematted_computation/bcijh,bcjhp->bcihp/dot_general:")
CKPT_BWD = (BODY + "vmap(transpose(jvp(local/fwd)))/while/body/closed_call/"
            "checkpoint/bcijh,bcjhp->bcihp/dot_general:")
UPDATE = BODY + "vmap(local/update)/add:"
SYNC = "jit(<lambda>)/sync/L2/reduce_sum:"
SCAN = "jit(<lambda>)/local/while/body/dynamic_update_slice:"
CAST = "jit(<lambda>)/local/while:"   # rebuilt by XLA with the loop's name
TOP = "jit(<lambda>)/concatenate:"


@pytest.mark.parametrize("path,scope", [
    (FWD, "local/fwd"), (BWD, "local/bwd"), (REMAT, "local/remat"),
    (CKPT_BWD, "local/bwd"),
    (UPDATE, "local/update"), (SYNC, "sync/L2"),
    ("jit(<lambda>)/sync/L1/encode/abs:", "sync/L1/encode"),
    ("jit(<lambda>)/sync/L1/decode/convert_element_type:", "sync/L1/decode"),
    ("jit(<lambda>)/sync/L1/collective/psum:", "sync/L1/collective"),
    ("jit(<lambda>)/sync/fold/sync/L2/concatenate:", "sync/fold"),
    ("jit(<lambda>)/probe/reshape:", "probe"),
    ("jit(<lambda>)/probed/add:", "unscoped"),
    (SCAN, "local"), (CAST, "local"), (TOP, "unscoped"), ("", "unscoped"),
    ("jit(<lambda>)/locality/add:", "unscoped"),
])
def test_scope_of_paths(path, scope):
    assert scope_of(path) == scope


def events():
    # TPU traces name an op by its HLO text
    scan = ("%while.1 = (f32[4,64]{1,0}, f32[4,64]{1,0}) while((f32[4,64]"
            "{1,0}, f32[4,64]{1,0}) %tuple.3), condition=%cond, body=%body")
    return [
        (DEV, OPS, scan, 0, 100, "jit(<lambda>)/local/while:"),
        (DEV, OPS, "fusion.1", 0, 20, FWD),
        (DEV, OPS, "fusion.2", 20, 30, BWD),
        (DEV, OPS, "fusion.3", 50, 10, UPDATE),
        (DEV, OPS, "%concatenate.4 = f32[8]{0} concatenate(f32[4]{0} %a, "
         "f32[4]{0} %b), dimensions={0}", 60, 5, TOP),
        (DEV, OPS, "fusion.5", 80, 15, SYNC),     # 65-80: only the while
        (DEV, OPS, "fusion.6", 135, 30, FWD),      # past the window's end
        (DEV, "XLA Modules", "jit_round", 0, 160, ""),
        (HOST, "python", "chipbench.run_rounds", 0, 145, {}),
        (HOST, "python", "hsgd.run_rounds", 2, 130, {"call": 0}),
        (HOST, "python", "hsgd.dispatch", 3, 50, {"call": 0}),
        (HOST, "python", "hsgd.history", 104, 26, {"call": 0}),
    ]


def test_scopes_split_the_busy_time():
    s = reduce_scopes(events())
    assert s.window_s == pytest.approx(145e-9)
    assert s.busy_s == pytest.approx(110e-9)
    assert s.scope_s == pytest.approx({
        "local/fwd": 30e-9, "local/bwd": 30e-9, "local/update": 10e-9,
        "sync/L2": 15e-9, "unscoped": 5e-9, "control": 20e-9})
    # the loop itself (HLO text ``%while.1 = ...``) is control flow
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s)
    # the same window and busy time as the reduction of trace_reduce
    t = reduce_events([e[:5] for e in events()])
    assert (t.window_s, t.busy_s) == pytest.approx((s.window_s, s.busy_s))
    assert s.unscoped_ops == [(f"concatenate.4 f32[8] | {TOP}",
                               pytest.approx(5e-9))]


def _space(tmp_path):
    """A trace of one program whose optimized HLO has the round's loop
    (named ``local/while``), a layer scan in the backward inside it, a
    loop XLA added without a name inside that, and ops with no path at
    each depth and in the entry computation."""
    from chipbench.scopes import message_class
    layer_scan = BODY + "vmap(transpose(jvp(local/fwd)))/while"
    comps = {   # id -> [(instruction, op_name, called computations)]
        1: [("while.1", "jit(<lambda>)/local/while", [2]),
            ("copy-start.3", "", [])],
        2: [("while.2", layer_scan, [3]), ("copy.7", "", [])],
        3: [("while.3", "", [4]), ("reduce-window.54", "", [])],
        4: [("copy.889", "", []), ("fusion.9", FWD[:-1], [])],
    }
    hlo = message_class("HloProto")()
    for cid, instrs in comps.items():
        comp = hlo.hlo_module.computations.add(id=cid)
        for name, path, called in instrs:
            ins = comp.instructions.add(name=name)
            ins.metadata.op_name = path
            ins.called_computation_ids.extend(called)
    space = message_class("XSpace")()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    md = meta.event_metadata.add(key=5).value
    md.name = "jit__lambda(4242)"
    md.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    dev = space.planes.add(name=DEV)
    for key, name in ((1, "tf_op"), (2, "program_id")):
        dev.stat_metadata.add(key=key).value.name = name
    line = dev.lines.add(name=OPS, timestamp_ns=0)
    ops = [("while.1", "", 0, 100), ("copy-start.3", "", 100, 5),
           ("while.2", "", 10, 60), ("copy.7", "", 5, 5),
           ("while.3", "", 20, 30), ("reduce-window.54", "", 12, 8),
           ("copy.889", "", 22, 3), ("fusion.9", FWD, 25, 20)]
    for key, (name, tf_op, start, dur) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=key).value
        md.name, md.display_name = f"%{name} = f32[8]{{0}} op()", name
        if tf_op:
            md.stats.add(metadata_id=1, str_value=tf_op)
        md.stats.add(metadata_id=2, uint64_value=4242)
        line.events.add(metadata_id=key, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    path = tmp_path / "held.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_an_op_without_a_path_takes_the_path_of_its_caller(tmp_path):
    evs = read_events(_space(tmp_path))
    meta = {e[2].split(" = ")[0][1:]: e[5] for e in evs}
    assert meta["copy.7"] == "jit(<lambda>)/local/while"
    assert meta["reduce-window.54"] == BODY + \
        "vmap(transpose(jvp(local/fwd)))/while"
    # the loop XLA added has no name: its caller's path
    assert meta["copy.889"] == meta["reduce-window.54"]
    assert meta["fusion.9"] == FWD          # a path of its own is kept
    assert meta["copy-start.3"] == ""       # the entry computation's
    s = reduce_scopes(evs)
    assert s.scope_s == pytest.approx({
        "local": 5e-9, "local/bwd": 11e-9, "local/fwd": 20e-9,
        "unscoped": 5e-9, "control": 64e-9})
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s)


def test_a_trace_with_no_local_time_is_refused():
    # what a program without scopes, or one loaded from a compile cache
    # filled before its scopes, gives
    evs = [e[:5] + ("",) if e[0] == DEV else e for e in events()]
    s = reduce_scopes(evs)
    assert s.scope_s["unscoped"] == pytest.approx(s.busy_s - 20e-9)
    with pytest.raises(NoScopes, match="compile cache"):
        per_step(s, steps=5)


def test_idle_time_goes_to_the_innermost_host_span():
    # the device idles 100-135: hsgd.run_rounds alone holds 100-104 and
    # 130-132, hsgd.history 104-130, no hsgd span 132-135
    s = reduce_scopes(events())
    assert s.host_gap_s == pytest.approx({"hsgd.history": 35e-9})
    assert s.calls == 1
    m = per_step(s, steps=5)
    assert m["local.fwd_ms"] == pytest.approx(1e3 * 30e-9 / 5)
    assert m["local.bwd_ms"] == pytest.approx(1e3 * 30e-9 / 5)
    assert m["local.update_ms"] == pytest.approx(1e3 * 10e-9 / 5)
    assert m["sync.ms"] == pytest.approx(1e3 * 15e-9 / 5)
    assert m["plan.host_gap_ms"] == pytest.approx(1e3 * 35e-9)


def test_host_span_of_a_gap():
    spans = [("hsgd.run_rounds", 0, 100), ("hsgd.dispatch", 10, 20),
             ("hsgd.dispatch", 20, 30)]
    assert host_span(spans, 12, 28) == "hsgd.dispatch"
    assert host_span(spans, 25, 60) == "hsgd.run_rounds"
    assert host_span(spans, 90, 200) == "outside hsgd"
    assert host_span([], 0, 1) == "outside hsgd"


def test_scopes_average_over_devices():
    evs = events() + [("/device:TPU:1", OPS, "fusion.7", 0, 40, UPDATE)]
    s = reduce_scopes(evs)
    assert s.scope_s["local/update"] == pytest.approx((10e-9 + 40e-9) / 2)
    assert s.busy_s == pytest.approx((110e-9 + 40e-9) / 2)


def test_host_only_trace_has_nothing_to_reduce():
    assert reduce_scopes([(HOST, "python", "hsgd.run_rounds", 0, 5,
                           {})]) is None


def test_decoder_reads_the_tf_op_of_a_recorded_trace():
    path = str(TESTDATA / "tiny.xplane.pb")
    evs = read_events(path)
    assert [e[:5] for e in evs] == profile_events(path)
    ops = {e[2].split(" = ")[0]: e[5] for e in evs
           if e[0] == DEV and e[1] == OPS}
    assert ops["%convolution_tanh_fusion"] == "jit(<lambda>)/dot_general:"
    assert reduce_scopes(evs).busy_s == reduce_events(
        profile_events(path)).busy_s


def test_recorded_scoped_trace():
    """Two calls of one global round of a tiny int8, probes-on,
    one-stale-level-2 engine on a TPU v5e (``record_scoped_trace.py``):
    every scope such a round has, the host spans with their arguments, and
    the scopes adding up to ``trace_reduce``'s busy time."""
    path = str(TESTDATA / "scoped.xplane.pb")
    evs = read_events(path)
    s = reduce_scopes(evs)
    t = reduce_events(profile_events(path))
    assert (s.window_s, s.busy_s) == (t.window_s, t.busy_s)
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert {"local", "local/fwd", "local/bwd", "local/update", "sync/L1",
            "sync/L1/encode", "sync/L1/decode", "sync/fold", "probe",
            "unscoped", "control"} <= set(s.scope_s)
    assert s.calls == 2
    spans = [(name, args) for _, _, name, _, _, args in evs
             if name.startswith("hsgd.")]
    assert [name for name, _ in spans] == 2 * [
        "hsgd.run_rounds", "hsgd.schedule", "hsgd.dispatch", "hsgd.dispatch",
        "hsgd.drain", "hsgd.history"]
    assert [args for name, args in spans if name == "hsgd.dispatch"] == [
        {"call": c, "n_local": 2, "level": lvl}
        for c in (8, 12) for lvl in (2, 1)]
    assert {args["call"] for _, args in spans} == {8, 12}
    assert set(s.host_gap_s) <= {"hsgd.dispatch", "hsgd.history",
                                 "hsgd.drain", "hsgd.schedule",
                                 "hsgd.run_rounds", "outside hsgd"}


# what the traced segment's split gave per step (``per_step`` at the
# segment's steps) on ``testdata/scoped.xplane.pb``: two calls of G=4
SCOPED_PER_STEP = {
    "local.fwd_ms": 0.017087624999999978, "local.bwd_ms": 0.03071049999999997,
    "local.update_ms": 0.0006657500000000002,
    "local.other_ms": 0.004732999999999997, "sync.ms": 0.011693125,
    "plan.host_gap_ms": 6.494713500000006}


def _ctx(summary, scopes, steps):
    import types
    return types.SimpleNamespace(trace=summary, scopes=scopes,
                                 traced_steps=steps)


@pytest.mark.parametrize("name", sorted(SCOPED_PER_STEP))
def test_step_reader_on_a_recorded_trace(name):
    from chipbench.harness import read_trace
    from chipbench.registry import metric_reader
    from chipbench.tests.tiny import REPO
    summary, s = read_trace(str(TESTDATA / "scoped.xplane.pb"))
    assert metric_reader(REPO, name)(_ctx(summary, s, 8)) == \
        SCOPED_PER_STEP[name]


@pytest.mark.parametrize("name", sorted(SCOPED_PER_STEP))
def test_step_reader_has_nothing_to_read_without_scopes(name):
    from chipbench.registry import metric_reader
    from chipbench.tests.tiny import REPO
    read = metric_reader(REPO, name)
    # a host-only backend records no device operation
    assert read(_ctx(None, None, 8)) is None
    # a program without scopes, or one from a cache filled without them
    evs = [e[:5] + ("",) if e[0] == DEV else e for e in events()]
    assert read(_ctx(None, reduce_scopes(evs), 5)) is None


def test_the_scopes_add_up_to_the_busy_time_per_step():
    from chipbench.harness import read_trace
    summary, s = read_trace(str(TESTDATA / "scoped.xplane.pb"))
    m = per_step(s, 8)
    rest = 1e3 * sum(v for k, v in s.scope_s.items()
                     if k in ("unscoped", "control", "probe")) / 8
    parts = sum(v for k, v in m.items() if k != "plan.host_gap_ms")
    assert parts + rest == pytest.approx(1e3 * summary.busy_s / 8, rel=1e-9)


def test_seconds_under_a_named_scope_inside_the_model():
    # a scope of a later family's layer (``moe``) in the forward, the
    # gradient and remat's recompute; ``moe_gate`` and ``premoe`` are not it
    moe = BODY + "vmap(jvp(local/fwd))/while/body/moe/dot_general:"
    evs = [
        (DEV, OPS, "fusion.1", 0, 20, moe),
        (DEV, OPS, "fusion.2", 20, 30, moe.replace("jvp(", "transpose(jvp(")),
        (DEV, OPS, "fusion.3", 50, 5, REMAT.replace("/bcijh", "/moe/bcijh")),
        (DEV, OPS, "fusion.4", 55, 7, moe.replace("/moe/", "/moe_gate/")),
        (DEV, OPS, "fusion.5", 62, 3, moe.replace("/moe/", "/premoe/")),
        (HOST, "python", "chipbench.run_rounds", 0, 100, {}),
    ]
    s = reduce_scopes(evs)
    assert under(s, "moe") == pytest.approx(
        {"fwd": 20e-9, "bwd": 30e-9, "remat": 5e-9})
    assert under(s, "moe_gate") == pytest.approx(
        {"fwd": 7e-9, "bwd": 0.0, "remat": 0.0})
    assert under(s, "nothing") == {"fwd": 0.0, "bwd": 0.0, "remat": 0.0}
    assert under(None, "moe") is None
    # the program's own scopes read the same way
    assert under(s, "local/fwd")["bwd"] == pytest.approx(30e-9)


def test_the_reader_knows_the_programs_span_names():
    from repro.obs import SPANS
    from chipbench.scopes import CALL_SPAN, HOST_PREFIX
    assert CALL_SPAN in SPANS
    assert all(name.startswith(HOST_PREFIX) for name in SPANS)


def test_the_reader_knows_the_programs_scope_names():
    from repro.obs import spans
    for name in (spans.LOCAL_FWD, spans.LOCAL_UPDATE, spans.SYNC_FOLD,
                 spans.sync_scope(3), spans.PROBE):
        assert scope_of(f"jit(<lambda>)/{name}/add:") == name
    assert scope_of(f"jit(<lambda>)/{spans.LOCAL}/while:") == spans.LOCAL
    for part in (spans.ENCODE, spans.COLLECTIVE, spans.DECODE):
        path = f"jit(<lambda>)/{spans.sync_scope(1)}/{part}/abs:"
        assert scope_of(path) == f"{spans.sync_scope(1)}/{part}"
