"""The trace reduction: busy union, idle gaps named after the harness's
host spans, collective time, on hand-made events and on a recorded TPU
trace (``testdata/tiny.xplane.pb``, made by ``record_trace.py``)."""
from pathlib import Path

import pytest

from chipbench.trace_reduce import profile_events, reduce_events, union

DEV, OPS = "/device:TPU:0", "XLA Ops"
TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_merges_overlaps():
    assert union([(5, 20), (0, 10), (30, 40), (40, 41)]) == [[0, 20],
                                                              [30, 41]]


def test_busy_union_and_named_gaps_on_hand_made_events():
    events = [
        (DEV, OPS, "fusion.1", 0, 10), (DEV, OPS, "fusion.2", 5, 15),
        (DEV, OPS, "all-reduce.3", 30, 10),
        (DEV, "XLA Modules", "jit_round", 0, 40),
        ("/host:CPU", "python", "chipbench.run_rounds", 0, 22),
        ("/host:CPU", "python", "chipbench.history", 22, 23),
    ]
    s = reduce_events(events)
    assert s.window_s == pytest.approx(45e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert s.idle_frac == pytest.approx(15 / 45)
    assert s.idle_gaps == [("idle in chipbench.history", pytest.approx(1e-8)),
                           ("idle in chipbench.history", pytest.approx(5e-9))]
    assert s.collective_s == {DEV: pytest.approx(1e-8)}
    assert s.top_ops[0] == ("fusion.2", pytest.approx(1.5e-8))


def test_host_only_trace_has_nothing_to_reduce():
    assert reduce_events([("/host:CPU", "python", "chipbench.block", 0, 5)]) \
        is None


def test_recorded_tpu_trace():
    """Two calls of three small matmuls, each followed by a 20 ms sleep in
    a ``chipbench.history`` span, recorded on a TPU v5e. The device clock
    runs about 1.2 ms ahead of the host spans in this recording, so the
    first call's operations fall before the window and only the second
    call's are counted."""
    s = reduce_events(profile_events(str(TESTDATA / "tiny.xplane.pb")))
    assert list(s.busy_s_per_device) == [DEV]
    assert s.window_s == pytest.approx(0.043587759, abs=1e-9)
    assert s.busy_s == pytest.approx(5.7673e-05, abs=1e-10)
    assert s.idle_gaps[0] == ("idle in chipbench.history",
                              pytest.approx(0.022489312, abs=1e-9))
    assert s.top_ops[0][0] == "convolution_tanh_fusion f32[1024,1024]"
    assert s.collective_s == {DEV: 0.0}


def test_spans_outside_the_harness_prefix_stay_out_of_the_window():
    # the call that starts the profiler runs under other names and is
    # left out: the window starts with the first ``chipbench.*`` span
    events = [
        (DEV, OPS, "fusion.1", 0, 10), (DEV, OPS, "fusion.2", 20, 10),
        ("/host:CPU", "python", "profiler_warm.run_rounds", 0, 2),
        ("/host:CPU", "python", "profiler_warm.block", 2, 9),
        ("/host:CPU", "python", "chipbench.run_rounds", 15, 16),
    ]
    s = reduce_events(events)
    assert s.window_s == pytest.approx(16e-9)
    assert s.busy_s == pytest.approx(10e-9)
    assert s.idle_gaps == [("idle in chipbench.run_rounds",
                            pytest.approx(5e-9)),
                           ("idle in chipbench.run_rounds",
                            pytest.approx(1e-9))]


def test_control_flow_counts_as_busy_but_not_among_the_top_ops():
    events = [
        (DEV, OPS, "while.9", 0, 30), (DEV, OPS, "fusion.1", 2, 10),
        (DEV, OPS, "fusion.2", 14, 6),
        ("/host:CPU", "python", "chipbench.run_rounds", 0, 40),
    ]
    s = reduce_events(events)
    assert s.busy_s == pytest.approx(30e-9)
    assert [name for name, _ in s.top_ops] == ["fusion.1", "fusion.2"]


def test_collective_time_counts_inside_the_window():
    # an all-reduce that starts before the window's first harness span and
    # one that runs past its end count only for their part inside it
    events = [
        (DEV, OPS, "all-reduce.1", 0, 10), (DEV, OPS, "fusion.1", 10, 10),
        (DEV, OPS, "all-reduce-start.2", 20, 10),
        ("/host:CPU", "python", "chipbench.run_rounds", 5, 20),
    ]
    s = reduce_events(events)
    assert s.collective_s == {DEV: pytest.approx(10e-9)}
    assert s.collective_s[DEV] <= s.busy_s_per_device[DEV]


def test_a_collective_is_told_by_its_hlo_opcode():
    # a TPU trace names each op by its HLO text; an all-reduce that a psum
    # made carries the psum's name, and an op that only reads one is none
    events = [
        (DEV, OPS, "%psum_invariant.15 = f32[1,18]{1,0:T(8,128)} "
         "all-reduce(%tanh_fusion), channel_id=1, replica_groups={{0,1}}",
         0, 10),
        (DEV, OPS, "%fusion.4 = f32[1,18]{1,0} fusion(%psum_invariant.15, "
         "%all-reduce.2), kind=kLoop, calls=%fused_computation", 10, 10),
        (DEV, OPS, "%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) "
         "collective-permute-start(%x), source_target_pairs={{0,1}}", 20, 5),
        ("/host:CPU", "python", "chipbench.run_rounds", 0, 25),
    ]
    s = reduce_events(events)
    assert s.collective_s == {DEV: pytest.approx(15e-9)}
    assert [n for n, _ in s.top_ops] == [
        "psum_invariant.15 f32[1,18]", "fusion.4 f32[1,18]",
        "collective-permute-start.1 f32[8]"]
