"""repro.analysis: the walker, the rule catalog (R1–R5) on hand-built
report fixtures AND live engines, the budget diff, and the CLI gate.

Every rule gets a good/bad fixture pair built from plain report data (no
tracers), plus a live demonstration where one device suffices: an injected
extra reduction is caught by R1, the legacy int8 encode→reduce(f32)→decode
roundtrip (``wire_reduce=False``) fires R2 while the default compressed
collective is clean, a
``jax.debug.print`` smuggled into the loss is caught by R3, and synthetic
budget regressions (extra sync op, dtype upcast, byte growth) fail the
check — the acceptance criteria of the analysis subsystem.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (EventAudit, Finding, RoundAudit, SyncPlanReport,
                            audit_engine, check_reports, entry_from_report,
                            fingerprint, run_rules, trace, update_budget,
                            walk, waivers_for)
from repro.analysis.__main__ import CONFIGS, build_engine, main
from repro.core.hsgd import HSGD
from repro.core.topology import HierarchySpec, make_topology
from repro.models.simple import SimpleConfig, SimpleModel
from repro.optim.optimizers import sgd


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------
def test_walker_records_collectives_with_axes_and_payload():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(), check_vma=False)
    summary = trace(jax.jit(f), jnp.ones((1, 4), jnp.float32))
    assert summary.collective_count == 1
    op = summary.collectives[0]
    assert op.primitive in ("psum", "psum2", "psum_invariant")
    assert op.axes == ("x",)
    assert op.dtypes == ("float32",)
    assert op.elements == 4 and op.nbytes == 16
    assert "shard_map" in op.path  # nested walk records the enclosure


def test_walker_records_host_callbacks():
    def g(x):
        jax.debug.print("sum={s}", s=x.sum())
        return x * 2

    summary = trace(g, jnp.ones(3))
    # jax.debug.print binds debug_print on JAX >= 0.9, debug_callback before
    assert [o.primitive for o in summary.callbacks] in (["debug_callback"],
                                                        ["debug_print"])


def test_walker_descends_into_scan_bodies():
    def f(x):
        def body(c, _):
            return c + x.sum(), None
        out, _ = jax.lax.scan(body, 0.0, jnp.arange(3.0))
        return out

    summary = trace(f, jnp.ones(4))
    assert any(o.primitive == "reduce_sum" and o.path.startswith("scan")
               for o in summary.reduces)


def test_fingerprint_stable_across_traces_and_sensitive_to_program():
    # grad-of-relu carries custom_jvp_call params whose pretty-print embeds
    # function object addresses — the fingerprint must scrub them
    f = lambda x: jax.grad(lambda y: jax.nn.relu(y).sum())(x)
    j1 = jax.make_jaxpr(f)(jnp.ones(3))
    j2 = jax.make_jaxpr(f)(jnp.ones(3))
    assert fingerprint(j1) == fingerprint(j2)
    j3 = jax.make_jaxpr(lambda x: x * 3)(jnp.ones(3))
    assert fingerprint(j1) != fingerprint(j3)


# ---------------------------------------------------------------------------
# rule fixtures (plain report data, no tracing)
# ---------------------------------------------------------------------------
def mk_event(key="L1", sync_ops=6, expected=6, dtypes=("float32",),
             nbytes=976, elements=244, expected_elements=None, axes=()):
    return EventAudit(key=key, level=int(key[1]), groups=None,
                      sync_ops=sync_ops, expected_sync_ops=expected,
                      ops=(), axes=tuple(axes), wire_dtypes=tuple(dtypes),
                      payload_elements=elements, payload_bytes=nbytes,
                      expected_payload_elements=expected_elements)


def mk_round(key="r4+L1", collectives=0, callbacks=(), transfers=(),
             cache_stable=True, cache_size=1):
    return RoundAudit(key=key, n_local=4, event=key.split("+")[1],
                      collective_count=collectives,
                      callbacks=tuple(callbacks), transfers=tuple(transfers),
                      cache_stable=cache_stable, jit_cache_size=cache_size)


def mk_report(events=(), rounds=(), codec=None, wire=None, config="fixture",
              waivers=()):
    report = SyncPlanReport(
        config=config, executor="sim", topology="UniformTopology",
        aggregator="MeanAggregator", codec=codec,
        events={e.key: e for e in events},
        rounds={r.key: r for r in rounds}, wire=wire)
    return dataclasses.replace(
        report, findings=tuple(run_rules(report, waivers)))


def rules_fired(report):
    return sorted({f.rule for f in report.findings})


def test_r1_sync_op_count():
    assert rules_fired(mk_report(events=[mk_event()])) == []
    assert rules_fired(mk_report(events=[mk_event(sync_ops=7)])) == ["R1"]
    # no exact expectation -> R1 defers to the budget
    assert rules_fired(
        mk_report(events=[mk_event(sync_ops=7, expected=None)])) == []


def test_r2_fires_on_f32_reduction_under_compressing_codec():
    # the deliberately-upcast codec fixture: int8 codec, f32 on the wire
    bad = mk_report(events=[mk_event()], codec="int8")
    assert rules_fired(bad) == ["R2"] and not bad.findings[0].waived
    # identity / comms-off configs move f32 legitimately
    assert rules_fired(mk_report(events=[mk_event()], codec="identity")) == []
    assert rules_fired(mk_report(events=[mk_event()], codec=None)) == []
    # a codec that actually ships int8 would pass
    assert rules_fired(
        mk_report(events=[mk_event(dtypes=("int8",))], codec="int8")) == []


def test_r2_waiver_suppresses_but_keeps_the_finding_visible():
    waived = mk_report(events=[mk_event()], codec="int8",
                       waivers={"R2": "baseline until compressed allreduce"})
    assert waived.unwaived == ()
    (f,) = waived.findings
    assert f.rule == "R2" and f.waived and "baseline" in f.waive_reason


def test_r3_host_callbacks_and_transfers():
    assert rules_fired(mk_report(rounds=[mk_round()])) == []
    bad = mk_report(rounds=[mk_round(callbacks=("debug_callback@pjit/scan",))])
    assert rules_fired(bad) == ["R3"]
    assert "debug_callback" in bad.findings[0].message
    assert rules_fired(
        mk_report(rounds=[mk_round(transfers=("device_put@pjit",))])) == ["R3"]


def test_r4_retrace_detection():
    assert rules_fired(mk_report(rounds=[mk_round(cache_size=1)])) == []
    assert rules_fired(mk_report(rounds=[mk_round(cache_size=3)])) == ["R4"]
    assert rules_fired(
        mk_report(rounds=[mk_round(cache_stable=False)])) == ["R4"]
    # unmeasured (no run_rounds pass) is not a finding
    assert rules_fired(mk_report(rounds=[mk_round(cache_size=None)])) == []


def test_r5_wire_accounting_cross_check():
    assert rules_fired(
        mk_report(events=[mk_event(expected_elements=244)])) == []
    assert rules_fired(
        mk_report(events=[mk_event(expected_elements=250)])) == ["R5"]


def test_report_json_roundtrip():
    rep = mk_report(events=[mk_event(axes=("pod", "data"))],
                    rounds=[mk_round(callbacks=("debug_callback@scan",))],
                    codec="int8",
                    wire={"payload_bytes": 248, "n_elements": 244,
                          "f32_bytes": 976, "wire_dtypes": ["float32", "int8"]})
    back = SyncPlanReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert back == rep


# ---------------------------------------------------------------------------
# live audits (sim executor, 1 device)
# ---------------------------------------------------------------------------
def test_live_audit_sim_off_matches_schedule():
    eng, state, batch_fn = build_engine("sim/two_level/off")
    rep = eng.audit(state, batch_fn, config="sim/two_level/off")
    assert set(rep.events) == {"L1", "L2"}
    for ev in rep.events.values():
        assert ev.sync_ops == ev.expected_sync_ops == 6  # mlp leaves
    assert rep.unwaived == ()
    # one compiled variant per round signature across run_rounds (R4 clean)
    assert {r.jit_cache_size for r in rep.rounds.values()} == {1}
    assert {r.cache_stable for r in rep.rounds.values()} == {True}


def test_live_audit_int8_r2_burned_down_by_wire_reduce():
    """The compressed-collective lowering keeps int8 on the wire (one int32
    psum-in-wire-dtype per bucket), so R2 passes with NO waiver; forcing
    the legacy roundtrip (``wire_reduce=False``) still fires it — the rule
    watches the lowering, not the codec declaration."""
    eng, state, _ = build_engine("sim/two_level/int8")
    rep = eng.audit(state)  # sync-only audit: no batch_fn needed for R2
    assert rep.unwaived == ()
    for ev in rep.events.values():
        assert "float32" not in ev.wire_dtypes
        assert ev.f32_elements == 0

    from repro.comms import Comms
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=8,
                                     num_classes=4))
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    legacy = HSGD(model.loss, sgd(0.1), topo,
                  comms=Comms("int8", wire_reduce=False))
    lstate = legacy.init(jax.random.PRNGKey(0), model.init)
    lrep = legacy.audit(lstate)
    assert sorted({f.rule for f in lrep.unwaived}) == ["R2"]
    waived = legacy.audit(lstate, waivers={"R2": "known baseline"})
    assert waived.unwaived == ()
    assert any(f.rule == "R2" and f.waived for f in waived.findings)


def test_live_injected_extra_reduction_caught_by_r1():
    """The synthetic regression of the acceptance criteria: an executor
    that sneaks one extra per-leaf reduction into every sync is caught by
    R1 (sync-op count doubles against the schedule prediction)."""
    from repro.core.executors import SimExecutor

    class ExtraReduceExecutor(SimExecutor):
        def sync_fn(self, event):
            base = super().sync_fn(event)

            def sync(params, opt_state, cstate, mask=None):
                p, o, c = base(params, opt_state, cstate, mask=mask)
                p = jax.tree.map(lambda x: x + 0.0 * x.sum(0, keepdims=True),
                                 p)
                return p, o, c

            return sync

    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=8,
                                     num_classes=4))
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    eng = HSGD(model.loss, sgd(0.1), topo, executor=ExtraReduceExecutor())
    state = eng.init(jax.random.PRNGKey(0), model.init)
    rep = eng.audit(state)
    assert sorted({f.rule for f in rep.unwaived}) == ["R1"]
    assert all(ev.sync_ops == 2 * ev.expected_sync_ops
               for ev in rep.events.values())


def test_live_debug_print_in_loss_caught_by_r3():
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=8,
                                     num_classes=4))

    def noisy_loss(params, batch):
        loss, metrics = model.loss(params, batch)
        jax.debug.print("loss={l}", l=loss)
        return loss, metrics

    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    eng = HSGD(noisy_loss, sgd(0.1), topo)
    state = eng.init(jax.random.PRNGKey(0), model.init)
    bf = lambda t: {"x": jnp.zeros((8, 4, 16), jnp.float32),
                    "y": jnp.zeros((8, 4), jnp.int32)}
    rep = audit_engine(eng, state, bf, run=False)  # trace only, no printing
    assert sorted({f.rule for f in rep.unwaived}) == ["R3"]
    assert any("debug_callback" in c or "debug_print" in c
               for r in rep.rounds.values() for c in r.callbacks)


# ---------------------------------------------------------------------------
# budget gating
# ---------------------------------------------------------------------------
def budget_for(report):
    return {"version": 1, "waivers": {},
            "configs": {report.config: entry_from_report(report)}}


def test_budget_unchanged_report_passes():
    rep = mk_report(events=[mk_event()], rounds=[mk_round()])
    regs, imps = check_reports([rep], budget_for(rep))
    assert regs == [] and imps == []


@pytest.mark.parametrize("mutate, expect", [
    (lambda e: mk_event(sync_ops=7, expected=None), "sync ops grew"),
    (lambda e: mk_event(dtypes=("float32", "float64")), "new wire dtype"),
    (lambda e: mk_event(nbytes=1952), "payload bytes grew"),
    (lambda e: mk_event(axes=("pod",)), "named axes changed"),
])
def test_budget_catches_synthetic_regressions(mutate, expect):
    """Extra sync op / f32->f64 upcast / byte growth / axis change injected
    over the pinned baseline all fail the check."""
    base = mk_report(events=[mk_event(axes=())])
    budget = budget_for(base)
    bad = mk_report(events=[mutate(None)])
    regs, _ = check_reports([bad], budget)
    assert any(expect in r for r in regs), (expect, regs)


def test_budget_catches_new_signatures_and_findings():
    base = mk_report(events=[mk_event()], rounds=[mk_round()])
    budget = budget_for(base)
    extra_event = mk_report(events=[mk_event(), mk_event(key="L2")],
                            rounds=[mk_round()])
    regs, _ = check_reports([extra_event], budget)
    assert any("new event signature 'L2'" in r for r in regs)
    # a waived finding passes the rules, but if the budget has not pinned
    # it, the check still flags it as new
    waived = mk_report(events=[mk_event()], rounds=[mk_round()],
                       codec="int8", waivers={"R2": "ok"})
    regs, _ = check_reports([waived], budget)
    assert any("new finding" in r for r in regs)


def test_budget_unwaived_finding_always_fails():
    bad = mk_report(events=[mk_event(sync_ops=7)])
    regs, _ = check_reports([bad], budget_for(bad))
    assert any("unwaived finding R1" in r for r in regs)


def test_budget_improvements_pass_with_note():
    base = mk_report(events=[mk_event()])
    better = mk_report(events=[mk_event(sync_ops=1, expected=1, nbytes=248)])
    regs, imps = check_reports([better], budget_for(base))
    assert regs == []
    assert any("shrank" in i for i in imps)


def test_budget_update_merges_and_preserves_waivers():
    old = {"version": 1,
           "waivers": {"*int8*": {"R2": "baseline"}},
           "configs": {"mesh/only": {"events": {}, "rounds": {},
                                     "wire": None, "findings": []}}}
    rep = mk_report(events=[mk_event()], config="sim/new")
    new = update_budget(old, [rep])
    assert new["waivers"] == old["waivers"]
    assert "mesh/only" in new["configs"]  # not re-audited -> kept verbatim
    assert new["configs"]["sim/new"] == entry_from_report(rep)
    assert waivers_for(new, "sim/two_level/int8") == {"R2": "baseline"}
    assert waivers_for(new, "sim/two_level/off") == {}


def test_budget_missing_config_is_a_regression():
    rep = mk_report(events=[mk_event()], config="unknown/config")
    regs, _ = check_reports([rep], {"version": 1, "waivers": {},
                                    "configs": {}})
    assert any("not in budget" in r for r in regs)


# ---------------------------------------------------------------------------
# CLI gate against the committed budget
# ---------------------------------------------------------------------------
def test_cli_check_passes_against_committed_budget(tmp_path):
    """The CI step, in miniature: audit runnable configs, diff against the
    committed ANALYSIS_budget.json, write the report artifact."""
    out = tmp_path / "report.json"
    rc = main(["--check", "--configs", "sim/two_level/off,sim/two_level/int8",
               "--out", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert "sim/two_level/off" in payload["configs"]
    # the compressed-collective burn-down: int8 is clean, nothing waived
    int8 = payload["configs"]["sim/two_level/int8"]
    assert int8["findings"] == []


def test_config_matrix_spans_the_lowering_paths():
    """Guard the matrix itself: both executors, comms off/identity/int8,
    and a multi-level schedule stay covered."""
    assert any(c.startswith("sim/") for c in CONFIGS)
    assert any(c.startswith("mesh/") for c in CONFIGS)
    assert any("three_level" in c for c in CONFIGS)
    assert any("int8" in c for c in CONFIGS)
    assert any("identity" in c for c in CONFIGS)
