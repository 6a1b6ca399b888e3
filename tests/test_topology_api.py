"""The unified topology/aggregation surface: SyncEvent schedules, pluggable
Aggregator strategies through both topologies, the make_topology registry,
and the schedule-compiled round executor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HSGD, CompressedAggregator, GroupedTopology, Grouping,
                        HierarchySpec, MeanAggregator, Round, SignSGDAggregator,
                        SyncEvent, UniformTopology, WeightedAggregator,
                        compile_schedule, contiguous, local_sgd, make_aggregator,
                        make_topology, run, two_level)
from repro.data import FederatedDataset, label_shard_partition, make_classification
from repro.models import SimpleConfig, SimpleModel
from repro.optim import sgd

N = 8


@pytest.fixture(scope="module")
def setup():
    x, y = make_classification(0, num_classes=8, dim=16, per_class=40)
    parts = label_shard_partition(y, [[j] for j in range(8)])
    ds = FederatedDataset(x, y, parts)
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=24,
                                     num_classes=8))
    return ds, model


def max_param_diff(a, b):
    d = jax.tree.map(lambda x, y: float(jnp.abs(x - y).max()), a, b)
    return max(jax.tree.leaves(d))


# ---------------------------------------------------------------------------
# SyncEvent schedules
# ---------------------------------------------------------------------------
def test_uniform_schedule_matches_period_arithmetic():
    """schedule(T) must encode exactly the old step_kind tuples: the highest
    level whose period divides t+1 (Algorithm D.1 break semantics)."""
    spec = HierarchySpec((2, 2, 2), (8, 4, 2))
    topo = UniformTopology(spec)
    sched = topo.schedule(16)
    for t, ev in enumerate(sched):
        lvl = next((l for l, p in enumerate(spec.periods, 1)
                    if (t + 1) % p == 0), None)
        assert ev == (None if lvl is None else SyncEvent(level=lvl)), (t, ev)


def test_grouped_schedule_matches_period_arithmetic():
    topo = GroupedTopology(contiguous(N, 2), G=8, I=(2, 4))
    for t, ev in enumerate(topo.schedule(16)):
        if (t + 1) % 8 == 0:
            assert ev == SyncEvent(level=1)
        else:
            groups = tuple(bool((t + 1) % Ii == 0) for Ii in (2, 4))
            if not any(groups):
                assert ev is None
            elif all(groups):
                assert ev == SyncEvent(level=2)
            else:
                assert ev == SyncEvent(level=2, groups=groups)


def test_events_are_hashable_jit_keys():
    a = SyncEvent(level=2, groups=(True, False))
    b = SyncEvent(level=2, groups=(True, False))
    assert a == b and hash(a) == hash(b) and a != SyncEvent(level=2)
    assert len({a, b, SyncEvent(level=1)}) == 2


def test_compile_schedule_folds_local_blocks():
    topo = make_topology("two_level", n=N, N=2, G=8, I=4)
    rounds = compile_schedule(topo.schedule(18))
    assert rounds == (Round(4, SyncEvent(level=2)), Round(4, SyncEvent(level=1)),
                      Round(4, SyncEvent(level=2)), Round(4, SyncEvent(level=1)),
                      Round(2, None))


# ---------------------------------------------------------------------------
# make_topology registry
# ---------------------------------------------------------------------------
def test_make_topology_registry():
    t1 = make_topology("uniform", spec=two_level(N, 2, 8, 2))
    t2 = make_topology("two_level", n=N, N=2, G=8, I=2)
    assert t1.schedule(8) == t2.schedule(8)
    t3 = make_topology("local_sgd", n=N, P=4)
    assert isinstance(t3, UniformTopology) and t3.periods == (4,)
    t4 = make_topology("grouped", grouping=contiguous(N, 2), G=8, I=2)
    assert isinstance(t4, GroupedTopology)
    # spec/grouping coercion
    assert isinstance(make_topology(local_sgd(N, 2)), UniformTopology)
    assert isinstance(make_topology(contiguous(N, 2), G=4, I=2),
                      GroupedTopology)
    with pytest.raises(KeyError):
        make_topology("ring")


def test_make_aggregator_resolution():
    assert isinstance(make_aggregator(None), MeanAggregator)
    assert isinstance(make_aggregator(None, sync_dtype="bfloat16"),
                      CompressedAggregator)
    assert make_aggregator(None, sync_dtype="float32").accum_dtype == jnp.float32
    assert isinstance(make_aggregator("sign"), SignSGDAggregator)
    inst = WeightedAggregator(np.ones(N))
    assert make_aggregator(inst) is inst
    with pytest.raises(KeyError):
        make_aggregator("median")


# ---------------------------------------------------------------------------
# every aggregator x both topologies through the single aggregate() entry
# ---------------------------------------------------------------------------
AGGS = [MeanAggregator(), CompressedAggregator(), SignSGDAggregator(),
        WeightedAggregator(np.arange(1, N + 1, dtype=float))]


@pytest.mark.parametrize("agg", AGGS, ids=lambda a: type(a).__name__)
@pytest.mark.parametrize("kind", ["uniform", "grouped"])
def test_aggregators_work_with_both_topologies(agg, kind):
    if kind == "uniform":
        topo = make_topology("uniform", spec=two_level(N, 2, 8, 4),
                             aggregator=agg)
    else:
        topo = make_topology("grouped", grouping=contiguous(N, 2), G=8, I=4,
                             aggregator=agg)
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 3, 5)), jnp.float32)}
    for ev in (SyncEvent(level=2), SyncEvent(level=1)):
        out = topo.aggregate(tree, ev)
        w = out["w"]
        assert w.shape == (N, 3, 5) and w.dtype == jnp.float32
        if ev.level == 1:  # global: every worker identical
            assert float(jnp.abs(w - w[0:1]).max()) == 0.0
        else:  # local: identical within each contiguous group of 4
            assert float(jnp.abs(w[:4] - w[0:1]).max()) == 0.0
            assert float(jnp.abs(w[4:] - w[4:5]).max()) == 0.0


def test_event_weights_match_weighted_aggregator():
    """Per-worker weights carried ON the event must weight the mean exactly
    like the same weights in a WeightedAggregator."""
    w = np.arange(1, N + 1, dtype=float)
    rng = np.random.default_rng(5)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 4)), jnp.float32)}
    for make in (lambda a: make_topology("uniform", spec=two_level(N, 2, 8, 4),
                                         aggregator=a),
                 lambda a: make_topology("grouped", grouping=contiguous(N, 2),
                                         G=8, I=4, aggregator=a)):
        via_event = make(None).aggregate(
            tree, SyncEvent(level=2, weights=tuple(w)))
        via_agg = make(WeightedAggregator(w)).aggregate(tree, SyncEvent(level=2))
        assert max_param_diff(via_event, via_agg) < 1e-6


def test_uniform_rejects_partial_group_events():
    topo = make_topology("two_level", n=N, N=2, G=8, I=4)
    with pytest.raises(AssertionError):
        topo.aggregate({"w": jnp.zeros((N, 2))},
                       SyncEvent(level=2, groups=(True, False)))


def test_named_aggregator_honours_sync_dtype():
    """--aggregator sign --sync-dtype bfloat16 must not silently run f32."""
    from repro.core import make_aggregator
    a = make_aggregator("sign", sync_dtype="bfloat16")
    assert a.accum_dtype == jnp.bfloat16
    b = make_aggregator("compressed", sync_dtype="float32")
    assert b.accum_dtype == jnp.float32


def test_sync_counts_match_comm_model():
    spec = HierarchySpec((2, 2, 2), (8, 4, 2))
    counts = spec.sync_counts(16)
    assert counts == (2, 2, 4)  # t+1 in {8,16} / {4,12} / {2,6,10,14}
    assert sum(counts) == sum(ev is not None
                              for ev in UniformTopology(spec).schedule(16))


def test_mean_and_weighted_agree_for_uniform_weights():
    tree = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(N, 4)),
                             jnp.float32)}
    for make in (lambda a: make_topology("uniform", spec=two_level(N, 2, 8, 4),
                                         aggregator=a),
                 lambda a: make_topology("grouped", grouping=contiguous(N, 2),
                                         G=8, I=4, aggregator=a)):
        m = make(MeanAggregator()).aggregate(tree, SyncEvent(level=1))
        w = make(WeightedAggregator(np.full(N, 0.25))).aggregate(
            tree, SyncEvent(level=1))
        assert max_param_diff(m, w) < 1e-6


def test_signsgd_majority_vote_semantics():
    topo = make_topology("local_sgd", n=4, P=1, aggregator="sign")
    x = jnp.asarray([[1.0], [2.0], [-3.0], [0.5]])
    out = topo.aggregate({"w": x}, SyncEvent(level=1))["w"]
    # majority of signs is +, magnitude is mean|x| = 1.625
    assert float(jnp.abs(out - 1.625).max()) < 1e-6
    tie = jnp.asarray([[1.0], [-1.0], [2.0], [-2.0]])
    out = topo.aggregate({"w": tie}, SyncEvent(level=1))["w"]
    assert float(jnp.abs(out).max()) == 0.0  # exact tie collapses to 0


def test_bf16_parity_between_topologies():
    """The compressed payload (once a Uniform-only flag) must produce the
    same aggregate through both topologies on a uniform grouping."""
    rng = np.random.default_rng(2)
    tree = {"w": jnp.asarray(rng.normal(size=(N, 6)), jnp.float32)}
    tu = make_topology("uniform", spec=two_level(N, 2, 8, 4),
                       sync_dtype="bfloat16")
    tg = make_topology("grouped", grouping=contiguous(N, 2), G=8, I=4,
                       sync_dtype="bfloat16")
    assert isinstance(tu.aggregator, CompressedAggregator)
    assert isinstance(tg.aggregator, CompressedAggregator)
    for ev in (SyncEvent(level=2), SyncEvent(level=1)):
        diff = max_param_diff(tu.aggregate(tree, ev), tg.aggregate(tree, ev))
        assert diff < 2e-2, (ev, diff)  # both bf16-rounded means


def test_masked_partial_participation_grouped_equivalence():
    """A (n,) participation mask on GroupedTopology must equal dropping the
    masked workers from the mean by hand (level 2) and the mean of
    participant group-means (level 1)."""
    g = contiguous(N, 2)
    topo = make_topology("grouped", grouping=g, G=8, I=4)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, 5)).astype(np.float32)
    mask = np.array([True, True, False, False, True, False, True, False])
    out = topo.aggregate({"w": jnp.asarray(x)}, SyncEvent(level=2),
                         mask=jnp.asarray(mask))["w"]
    for i in range(g.N):
        members = g.members(i)
        expect = x[members][mask[members]].mean(0)
        np.testing.assert_allclose(np.asarray(out[members]),
                                   np.tile(expect, (len(members), 1)),
                                   rtol=1e-5)
    out = topo.aggregate({"w": jnp.asarray(x)}, SyncEvent(level=1),
                         mask=jnp.asarray(mask))["w"]
    gm = np.stack([x[g.members(i)][mask[g.members(i)]].mean(0)
                   for i in range(g.N)])
    np.testing.assert_allclose(np.asarray(out), np.tile(gm.mean(0), (N, 1)),
                               rtol=1e-5)


def test_masked_uniform_matches_masked_grouped(setup):
    """Same mask, same uniform grouping => same trained params through
    either topology's masked path.  (Participation is balanced across
    groups: uniform's global mean is a flat participant mean, grouped's is a
    mean of group means — they only coincide at equal per-group counts.)"""
    ds, model = setup
    mask = np.array([True, True, False, False, True, False, True, False])

    def train(topo):
        eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
        st = eng.init(jax.random.PRNGKey(0), model.init)
        for t in range(8):
            st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                             mask=mask)
        return st

    s1 = train(make_topology("uniform", spec=two_level(N, 2, 8, 4)))
    s2 = train(make_topology("grouped", grouping=contiguous(N, 2), G=8, I=4))
    assert max_param_diff(s1.params, s2.params) < 1e-5


# ---------------------------------------------------------------------------
# schedule-compiled executor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("topo_fn", [
    lambda: make_topology("two_level", n=N, N=2, G=8, I=4),
    lambda: make_topology("uniform",
                          spec=HierarchySpec((2, 2, 2), (8, 4, 2))),
    lambda: make_topology("grouped", grouping=contiguous(N, 2), G=8, I=(2, 4)),
    lambda: make_topology("two_level", n=N, N=2, G=8, I=4, aggregator="sign"),
], ids=["two_level", "three_level", "grouped_hetero", "sign"])
def test_run_rounds_equals_per_step(setup, topo_fn):
    """run_rounds must reproduce the per-step step() trajectory bitwise."""
    ds, model = setup
    batch_fn = lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8))
    T = 18  # includes a trailing partial round

    eng_a = HSGD(model.loss, sgd(0.05), topo_fn(), jit=True)
    st_a = eng_a.init(jax.random.PRNGKey(0), model.init)
    step_metrics = []
    for t in range(T):
        st_a, m = eng_a.step(st_a, batch_fn(t))
        step_metrics.append({k: float(v) for k, v in m.items()})

    eng_b = HSGD(model.loss, sgd(0.05), topo_fn(), jit=True)
    st_b = eng_b.init(jax.random.PRNGKey(0), model.init)
    st_b, hist = eng_b.run_rounds(st_b, batch_fn, T)

    assert max_param_diff(st_a.params, st_b.params) == 0.0
    assert int(st_b.step) == T
    assert [rec["t"] for rec in hist] == list(range(1, T + 1))
    for rec, m in zip(hist, step_metrics):
        assert abs(rec["ce"] - m["ce"]) < 1e-5


def test_run_rounds_resumes_mid_schedule(setup):
    """Starting run_rounds from a nonzero state.step must continue the
    schedule phase-correctly (events depend on absolute t)."""
    ds, model = setup
    batch_fn = lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8))
    topo = make_topology("two_level", n=N, N=2, G=8, I=4)

    eng_a = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st_a = eng_a.init(jax.random.PRNGKey(0), model.init)
    for t in range(16):
        st_a, _ = eng_a.step(st_a, batch_fn(t))

    eng_b = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st_b = eng_b.init(jax.random.PRNGKey(0), model.init)
    st_b, _ = eng_b.run_rounds(st_b, batch_fn, 6)   # ends mid-round
    st_b, _ = eng_b.run_rounds(st_b, batch_fn, 10)  # resumes at t=6
    assert max_param_diff(st_a.params, st_b.params) == 0.0


def test_run_rounds_eval_at_boundaries(setup):
    ds, model = setup
    batch_fn = lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8))
    topo = make_topology("two_level", n=N, N=2, G=8, I=4)
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    st, hist = eng.run_rounds(st, batch_fn, 16, eval_every=8,
                              eval_fn=lambda s, t: {"evaluated_at": t + 1})
    assert [r["t"] for r in hist if "evaluated_at" in r] == [8, 16]


def test_run_records_per_step_metrics(setup):
    """run() history must not be empty without eval_every (regression)."""
    ds, model = setup
    topo = make_topology("two_level", n=N, N=2, G=4, I=2)
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    st, hist = run(eng, st, lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                   T=6)
    assert len(hist) == 6
    assert all("ce" in rec and rec["t"] == i + 1 for i, rec in enumerate(hist))
    # eval results merge into the matching step's record
    st, hist = run(eng, st, lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                   T=4, eval_every=2, eval_fn=lambda s, t: {"ev": True})
    assert [("ev" in rec) for rec in hist] == [False, True, False, True]


def test_grouped_topology_size_weighted_global():
    """Grouping.size_weights through WeightedAggregator reproduces the
    unweighted-mean-of-group-means on a NON-uniform grouping at level 2."""
    g = Grouping((0, 0, 0, 1, 1, 2, 2, 2))
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    topo = make_topology("grouped", grouping=g, G=4, I=2,
                         aggregator=WeightedAggregator(g.size_weights()))
    out = topo.aggregate({"w": x}, SyncEvent(level=2))["w"]
    a = np.asarray(g.assignment)
    for i in range(g.N):  # weights are constant within a group => group mean
        np.testing.assert_allclose(np.asarray(out[a == i]),
                                   np.tile(np.asarray(x[a == i]).mean(0),
                                           (sum(a == i), 1)), rtol=1e-5)


# ---------------------------------------------------------------------------
# denominator guards (accumulation-dtype-aware)
# ---------------------------------------------------------------------------
def test_denominator_guard_survives_half_precision_all_masked():
    """The weighted-mean denominator floor must live in the ACCUMULATION
    dtype: the old literal ``1e-9`` underflows to 0 in f16 accumulation, so
    an all-masked group divided 0/0 = NaN.  With ``denominator_floor`` the
    quotient is an exact, finite 0 in every accumulation dtype."""
    from repro.core.aggregators import (axis_weighted_mean, denominator_floor,
                                        named_axis_weighted_mean,
                                        segment_weighted_mean)
    # the bug being fixed: the old guard is literally zero in f16
    assert float(jnp.asarray(1e-9, jnp.float16)) == 0.0
    for acc in (jnp.float16, jnp.bfloat16, jnp.float32):
        assert float(denominator_floor(acc)) > 0.0

    v = jnp.ones((4, 3), jnp.float16)
    w = jnp.zeros((4, 1), jnp.float16)          # every worker masked out
    out = axis_weighted_mean(v, w, (0,), jnp.float16)
    assert np.isfinite(np.asarray(out, jnp.float32)).all()

    membership = jnp.asarray(np.eye(2).repeat(2, axis=1), jnp.float16)
    out = segment_weighted_mean(v, jnp.zeros((4,), jnp.float16), membership,
                                jnp.float16)
    assert np.isfinite(np.asarray(out, jnp.float32)).all()

    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = jax.shard_map(
        lambda vv, ww: named_axis_weighted_mean(vv, ww[0], ("x",),
                                                jnp.float16),
        mesh=mesh, in_specs=(P("x"), P("x")), out_specs=P("x"),
        check_vma=False)
    out = f(jnp.ones((1, 3), jnp.float16), jnp.zeros((1,), jnp.float16))
    assert np.isfinite(np.asarray(out, jnp.float32)).all()


def test_denominator_guard_keeps_f32_weighted_means_exact():
    """For real (nonzero) f32 weight sums the floor never engages, so the
    fix is bitwise-invisible to every existing weighted trajectory."""
    from repro.core.aggregators import axis_weighted_mean
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(8, 1)), jnp.float32)
    got = axis_weighted_mean(v, w, (0,), jnp.float32)
    want = (v * w).sum(0, keepdims=True) / w.sum(0, keepdims=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
