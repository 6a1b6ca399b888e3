"""The comms subsystem: FlatBucket fusion, codec kernels vs oracles, the
registry, WireStats accounting, and engine integration (sim executor).

Sim<->mesh comms equivalence lives in tests/test_executors.py (needs 8
devices); codec round-trip/idempotence property tests in
tests/test_comms_properties.py (hypothesis-optional)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comms import (Comms, Compressor, FlatBucket, IdentityCompressor,
                         Int8Compressor, SignCompressor, TopKCompressor,
                         WireArray, WireStats, make_comms, make_compressor,
                         register_compressor)
from repro.comms.codecs import COMPRESSORS
from repro.core import (HSGD, GroupedTopology, HierarchySpec, SyncEvent,
                        contiguous, make_aggregator, make_topology)
from repro.data import FederatedDataset, label_shard_partition, make_classification
from repro.comms.reduce import topk_decode_reduce
from repro.kernels.comms import (int8_dequantize, int8_quantize,
                                 int8_scale_quantize, sign_pack, sign_unpack)
from repro.kernels.ref import int8_ref, int8_scale_quant_ref, sign_ref
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


def trajectory(ds, model, topo, comms, T=16, executor="sim"):
    eng = HSGD(model.loss, sgd(0.05), topo, executor=executor, comms=comms)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    st, hist = eng.run_rounds(
        st, lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8)), T)
    return eng, st, hist


def max_diff(a, b):
    d = jax.tree.map(lambda x, y: float(jnp.abs(x - y).max()), a, b)
    return max(jax.tree.leaves(d))


# ---------------------------------------------------------------------------
# FlatBucket
# ---------------------------------------------------------------------------
def tree_mixed(n=4):
    rng = np.random.default_rng(0)
    return {
        "w1": jnp.asarray(rng.normal(size=(n, 5, 3)), jnp.float32),
        "b1": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
        "h": jnp.asarray(rng.normal(size=(n, 2, 2)), jnp.bfloat16),
        "s": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
    }


def test_flatbucket_roundtrip_mixed_dtypes():
    tree = tree_mixed()
    fb = FlatBucket.plan(tree)
    bufs = fb.flatten(tree)
    assert sorted(bufs) == ["bfloat16", "float32"]
    assert bufs["float32"].shape == (4, 15 + 3 + 1)
    assert bufs["bfloat16"].shape == (4, 4)
    out = fb.unflatten(bufs)
    assert max_diff(tree, out) == 0.0
    assert jax.tree.map(lambda x: x.dtype, out) == \
        jax.tree.map(lambda x: x.dtype, tree)


def test_flatbucket_plan_is_cached():
    tree = tree_mixed()
    assert FlatBucket.plan(tree) is FlatBucket.plan(tree_mixed())


def test_flatbucket_per_shard_worker_axis():
    """The mesh executor flattens (1, ...) shards with their own plan."""
    tree = jax.tree.map(lambda x: x[:1], tree_mixed())
    fb = FlatBucket.plan(tree)
    assert fb.lengths == FlatBucket.plan(tree_mixed()).lengths
    assert max_diff(tree, fb.unflatten(fb.flatten(tree))) == 0.0


# ---------------------------------------------------------------------------
# kernels vs jnp oracles (interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,c,blk", [(3, 100, 32), (1, 64, 64), (4, 37, 16),
                                     (2, 8, 8), (1, 7, 8)])
def test_int8_kernels_match_ref(r, c, blk):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    q, s = int8_quantize(x, block=blk, interpret=True)
    qr, sr, rtr = int8_ref(x, blk)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = int8_dequantize(q, s, block=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(rtr), rtol=1e-6)
    # per-block max-scale error bound
    xb = np.asarray(x)
    err = np.abs(np.asarray(y) - xb).max()
    assert err <= np.abs(xb).max() / 127.0 * 0.5 + 1e-6


@pytest.mark.parametrize("r,c,blk", [(3, 100, 32), (1, 64, 64), (4, 37, 16),
                                     (2, 8, 8)])
def test_sign_kernels_match_ref(r, c, blk):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    bits, s = sign_pack(x, block=blk, interpret=True)
    assert bits.dtype == jnp.uint8
    sr, rtr = sign_ref(x, blk)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = sign_unpack(bits, s, size=c, block=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(rtr), rtol=1e-6)
    # decoded values are exactly +-(block mean |x|), sign-aligned with x
    assert (np.sign(np.asarray(y)) == np.where(np.asarray(x) >= 0, 1, -1)).all()


@pytest.mark.parametrize("r,c,blk", [(3, 100, 32), (1, 64, 64), (4, 37, 16),
                                     (2, 8, 8), (1, 7, 8)])
def test_int8_scale_quantize_matches_ref(r, c, blk):
    """The shared-scale quantizer of the compressed allreduce: the caller
    supplies per-block scales (the group amax under the collective), the
    kernel must reproduce the jnp oracle exactly — including a zero scale
    mapping to q = 0."""
    rng = np.random.default_rng(4)
    nb = -(-c // blk)
    x = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    scale = jnp.asarray(np.abs(rng.normal(size=(r, nb))), jnp.float32)
    scale = scale.at[:, 0].set(0.0)  # exercise the zero-scale branch
    q = int8_scale_quantize(x, scale, block=blk, interpret=True)
    assert q.dtype == jnp.int8 and q.shape == (r, c)
    np.testing.assert_array_equal(np.asarray(q),
                                  np.asarray(int8_scale_quant_ref(x, scale,
                                                                  blk)))
    assert not np.asarray(q[:, :min(blk, c)]).any()  # zero scale -> q = 0


@pytest.mark.parametrize("m,k,size,blk", [(8, 4, 100, 32), (1, 1, 7, 8),
                                          (16, 15, 244, 64), (3, 10, 64, 64)])
def test_topk_decode_reduce_matches_ref(m, k, size, blk):
    """The scatter-add decode-reduce behind the top-k ragged all-gather:
    M sparse (values, indices) payloads summed into one dense (size,) f32
    buffer.  It is the same jnp scatter as ``kernels/ref.py::topk_reduce_ref``,
    so it is held to numpy's unbuffered ``np.add.at`` instead: distinct
    indices land bitwise (each output element is one payload value);
    colliding indices accumulate up to f32 summation order; a masked-out
    payload (values zeroed, as ``MeshWireOps.sparse_mean`` does) contributes
    nothing; and a gather delivered in ``blk``-entry pieces sums to the
    whole."""
    rng = np.random.default_rng(5)
    vals = rng.normal(size=(m, k)).astype(np.float32)

    def oracle(v, i):
        out = np.zeros(size, np.float32)
        np.add.at(out, i.reshape(-1), v.reshape(-1))
        return out

    uniq = rng.permutation(size)[:min(m * k, size)].astype(np.int32)
    if uniq.size == m * k:  # all indices distinct -> bitwise
        out = topk_decode_reduce(jnp.asarray(vals),
                                 jnp.asarray(uniq.reshape(m, k)), size=size)
        assert out.shape == (size,) and out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out),
                                      oracle(vals, uniq.reshape(m, k)))
    idx = rng.integers(0, size, size=(m, k)).astype(np.int32)
    out = topk_decode_reduce(jnp.asarray(vals), jnp.asarray(idx), size=size)
    assert out.shape == (size,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), oracle(vals, idx),
                               rtol=1e-6, atol=1e-6)
    mask = (np.arange(m) % 2 == 0).astype(np.float32)[:, None]
    masked = topk_decode_reduce(jnp.asarray(vals * mask), jnp.asarray(idx),
                                size=size)
    np.testing.assert_allclose(np.asarray(masked),
                               oracle(vals[::2], idx[::2]),
                               rtol=1e-6, atol=1e-6)
    pieces = sum(topk_decode_reduce(jnp.asarray(vals[:, i:i + blk]),
                                    jnp.asarray(idx[:, i:i + blk]), size=size)
                 for i in range(0, k, blk))
    np.testing.assert_allclose(np.asarray(pieces), np.asarray(out),
                               rtol=1e-6, atol=1e-6)


def test_comm_kernels_public_entry_points():
    """ops.py exports with interpret-mode auto-selection + block shrinking."""
    from repro.kernels import (int8_dequantize as deq, int8_quantize as quant,
                               sign_pack as sp, sign_unpack as su)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 200)),
                    jnp.float32)
    q, s = quant(x)          # interpret auto-selected off-TPU, block shrunk
    assert q.shape == (2, 200) and s.shape[0] == 2
    y = deq(q, s)
    assert np.abs(np.asarray(y) - np.asarray(x)).max() < 0.05
    bits, ss = sp(x)
    ys = su(bits, ss, size=200)
    assert ys.shape == (2, 200)


# ---------------------------------------------------------------------------
# codec registry
# ---------------------------------------------------------------------------
def test_make_compressor_registry():
    assert isinstance(make_compressor(None), IdentityCompressor)
    assert isinstance(make_compressor("int8"), Int8Compressor)
    assert isinstance(make_compressor("sign", block=64), SignCompressor)
    assert isinstance(make_compressor("topk", rate=0.5), TopKCompressor)
    inst = Int8Compressor(block=64)
    assert make_compressor(inst) is inst
    with pytest.raises(KeyError):
        make_compressor("zstd")
    with pytest.raises(ValueError, match="constructing by name"):
        make_compressor(inst, block=32)

    class Noop(IdentityCompressor):
        name = "noop"

    register_compressor("noop", Noop)
    try:
        assert isinstance(make_compressor("NOOP"), Noop)
    finally:
        COMPRESSORS.pop("noop")


def test_make_comms_spellings():
    assert make_comms(None) is None
    c = make_comms("int8")
    assert isinstance(c, Comms) and isinstance(c.codec, Int8Compressor)
    assert make_comms(c) is c
    c2 = make_comms(SignCompressor(block=64))
    assert isinstance(c2.codec, SignCompressor)
    assert make_comms(bucket=True).bucket  # kwargs-only: identity + buckets


def test_make_aggregator_rejects_sync_dtype_on_instance():
    """Regression: sync_dtype was silently ignored when an instance was
    passed — now a clear ValueError."""
    inst = make_aggregator("mean")
    with pytest.raises(ValueError, match="sync_dtype"):
        make_aggregator(inst, sync_dtype="bfloat16")
    assert make_aggregator(inst) is inst  # no sync_dtype: unchanged


# ---------------------------------------------------------------------------
# WireStats
# ---------------------------------------------------------------------------
def test_wirestats_per_level_counts_uniform():
    topo = make_topology("uniform", spec=HierarchySpec((2, 2, 2), (8, 4, 2)))
    comms = Comms("identity")
    params = jax.tree.map(lambda x: jnp.broadcast_to(x, (8,) + x.shape),
                          {"w": jnp.zeros((10,), jnp.float32)})
    payload, n_el = comms.payload_spec(params)
    ws = WireStats(topo, payload, n_el)
    assert ws.payload_bytes == 40 and ws.f32_bytes == 40
    # level-l sync moves one payload per tree edge at tiers >= l
    assert ws.payload_count(SyncEvent(level=1)) == 2 + 4 + 8
    assert ws.payload_count(SyncEvent(level=2)) == 4 + 8
    assert ws.payload_count(SyncEvent(level=3)) == 8
    per = ws.per_level()
    assert per["L1"]["bytes_per_sync"] == 14 * 40
    assert per["L3"]["period"] == 2
    # schedule totals: periods (8,4,2) over 8 steps -> L3 at t=1,5 (2x),
    # L2 at t=3 (1x), L1 at t=7 (1x)
    sb = ws.step_bytes(8)
    assert sb == [0, 8 * 40, 0, 12 * 40, 0, 8 * 40, 0, 14 * 40]
    s = ws.summary(8)
    assert s["total_bytes"] == sum(sb)


def test_wirestats_grouped_topology():
    g = contiguous(6, 2)  # 2 groups of 3
    topo = GroupedTopology(g, G=8, I=(2, 4))
    ws = WireStats(topo, (), 0)
    assert ws.payload_count(SyncEvent(level=1)) == 6 + 2
    assert ws.payload_count(SyncEvent(level=2)) == 6
    assert ws.payload_count(SyncEvent(level=2, groups=(True, False))) == 3
    # heterogeneous periods: per_level costs the ACTUAL (partial) events —
    # I=(2, 8): three (True, False) L2 events per period, never a full one
    topo2 = GroupedTopology(g, G=8, I=(2, 8))
    wa = WireArray("value", (10,), "float32")
    ws2 = WireStats(topo2, (wa,), 10)
    per = ws2.per_level()
    assert per["L2"]["payloads_per_sync"] == 3       # one group of 3
    assert per["L2"]["syncs_per_period"] == 3
    assert per["L2"]["bytes_per_sync"] == 3 * wa.nbytes
    assert per["L1"]["payloads_per_sync"] == 8
    # summary and per-step history agree
    assert sum(ws2.step_bytes(8)) == \
        3 * per["L2"]["bytes_per_sync"] + per["L1"]["bytes_per_sync"]


def test_wirestats_codec_ratios():
    comms8 = Comms("int8")
    commsS = Comms("sign")
    params = {"w": jnp.zeros((8, 4096), jnp.float32)}
    topo = make_topology("two_level", n=8, N=2, G=8, I=2)
    for comms, lo, hi in [(comms8, 3.8, 4.1), (commsS, 28.0, 33.0)]:
        payload, n_el = comms.payload_spec(params)
        ws = WireStats(topo, payload, n_el)
        assert lo < ws.compression_ratio < hi, (comms, ws.compression_ratio)


# ---------------------------------------------------------------------------
# engine integration (sim)
# ---------------------------------------------------------------------------
def test_comms_off_is_default_and_stateless(setup):
    ds, model = setup
    topo = make_topology("two_level", n=N, N=2, G=8, I=4)
    eng, st, hist = trajectory(ds, model, topo, None)
    assert eng.comms is None and st.comms is None
    assert eng.wire_stats(st) is None
    assert "wire_bytes" not in hist[0]


def test_identity_bucket_is_bitwise(setup):
    """FlatBucket + identity codec only changes layout, never values."""
    ds, model = setup
    mk = lambda: make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    _, s0, h0 = trajectory(ds, model, mk(), None)
    e1, s1, h1 = trajectory(ds, model, mk(), Comms())
    assert max_diff(s0.params, s1.params) == 0.0
    assert [r["ce"] for r in h0] == [r["ce"] for r in h1]


def test_sync_operand_count_is_o_dtypes(setup):
    """The jaxpr of the fused aggregation shows O(dtypes) sync reductions
    instead of O(leaves) — the FlatBucket claim, verified on the lowered
    program (not wall-clock) via the repro.analysis walker."""
    from repro.analysis import trace
    ds, model = setup
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (N,) + x.shape),
        model.init(jax.random.PRNGKey(0)))
    n_leaves = len(jax.tree.leaves(params))
    assert n_leaves >= 4
    ev = SyncEvent(level=1)

    comms = Comms()
    plain = trace(lambda t: topo.aggregate(t, ev), params)
    fused = trace(
        lambda t: comms.sync(t, lambda b: topo.aggregate(b, ev))[0], params)
    assert plain.count("reduce_sum") == n_leaves
    assert fused.count("reduce_sum") == 1  # one f32 bucket


def test_int8_comms_trains(setup):
    ds, model = setup
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    eng, st, hist = trajectory(ds, model, topo, "int8")
    assert np.isfinite(hist[-1]["ce"])
    ws = eng.wire_stats(st)
    assert 3.8 < ws.compression_ratio < 4.1
    # history wire_bytes matches the static schedule accounting
    assert [r["wire_bytes"] for r in hist] == ws.step_bytes(len(hist))


def test_sign_comms_trains(setup):
    ds, model = setup
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    eng, st, hist = trajectory(ds, model, topo, Comms("sign", block=256))
    assert np.isfinite(hist[-1]["ce"])


def test_codec_composes_with_sign_aggregator(setup):
    """Codec (wire format) and aggregator (mean rule) are orthogonal."""
    ds, model = setup
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)),
                         aggregator="sign")
    eng, st, hist = trajectory(ds, model, topo, "int8")
    assert np.isfinite(hist[-1]["ce"])


def test_comms_on_grouped_topology(setup):
    ds, model = setup
    topo = GroupedTopology(contiguous(N, 2), G=8, I=4)
    eng, st, hist = trajectory(ds, model, topo, "int8")
    assert np.isfinite(hist[-1]["ce"])
    assert hist[7]["wire_bytes"] > hist[3]["wire_bytes"] > 0


def test_partial_group_events_keep_nonsyncing_workers(setup):
    """Regression: a lossy codec must not touch workers a partial-group
    event did not sync.  With I=(2, 8) group 1 never syncs before t=8, so
    its workers' params (and residuals) stay bitwise equal to the comms-off
    trajectory through t=7."""
    ds, model = setup
    mk = lambda: GroupedTopology(contiguous(N, 2), G=8, I=(2, 8))
    # group 0 syncs at t+1 in {2,4,6}; group 1 first syncs at t+1=8
    assert mk().event_at(1).groups == (True, False)
    _, s_off, _ = trajectory(ds, model, mk(), None, T=7)
    eng, s_on, _ = trajectory(ds, model, mk(), Comms("topk", rate=0.1), T=7)
    g1 = jax.tree.map(lambda x: x[4:], s_off.params)
    g1c = jax.tree.map(lambda x: x[4:], s_on.params)
    assert max_diff(g1, g1c) == 0.0
    # group 1's error-feedback residual is unconsumed (still zero)
    res = jax.tree.leaves(s_on.comms)
    assert all(float(jnp.abs(r[4:]).max()) == 0 for r in res)
    assert any(float(jnp.abs(r[:4]).max()) > 0 for r in res)
    # group 0 DID go through the codec
    g0 = jax.tree.map(lambda x: x[:4], s_off.params)
    g0c = jax.tree.map(lambda x: x[:4], s_on.params)
    assert max_diff(g0, g0c) > 0


def test_wire_stats_counts_optimizer_moments(setup):
    """Regression: aggregate_opt_state puts the moments on the wire, so the
    accounting must include them (sgd has none; momentum doubles params)."""
    from repro.optim import momentum
    ds, model = setup
    mk = lambda: make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    e_sgd = HSGD(model.loss, sgd(0.05), mk(), comms="int8")
    s_sgd = e_sgd.init(jax.random.PRNGKey(0), model.init)
    e_mom = HSGD(model.loss, momentum(0.05), mk(), comms="int8")
    s_mom = e_mom.init(jax.random.PRNGKey(0), model.init)
    b_sgd = e_sgd.wire_stats(s_sgd).payload_bytes
    b_mom = e_mom.wire_stats(s_mom).payload_bytes
    assert b_mom == 2 * b_sgd
    names = {a.name for a in e_mom.wire_stats(s_mom).payload}
    assert any(n.startswith("moments.") for n in names)
    # opting out of moment aggregation drops them from the accounting
    e_solo = HSGD(model.loss, momentum(0.05), mk(), comms="int8",
                  aggregate_opt_state=False)
    s_solo = e_solo.init(jax.random.PRNGKey(0), model.init)
    assert e_solo.wire_stats(s_solo).payload_bytes == b_sgd


def test_topk_error_feedback_state(setup):
    ds, model = setup
    mk = lambda: make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    eng, st, hist = trajectory(ds, model, mk(), Comms("topk", rate=0.25))
    assert st.comms is not None
    res = jax.tree.leaves(st.comms)
    assert all(r.dtype == jnp.float32 for r in res)
    assert max(float(jnp.abs(r).max()) for r in res) > 0  # EF accumulated
    assert np.isfinite(hist[-1]["ce"])
    # rate=1 keeps everything: EF machinery must be exactly transparent
    _, s_full, _ = trajectory(ds, model, mk(), Comms("topk", rate=1.0))
    _, s_off, _ = trajectory(ds, model, mk(), None)
    assert max_diff(s_full.params, s_off.params) == 0.0


def test_step_matches_rounds_with_comms(setup):
    """Per-step dispatch and the round executor agree bitwise under comms
    (residual state threads identically)."""
    ds, model = setup
    batch_fn = lambda t: jax.tree.map(jnp.asarray, ds.batch(t, 8))
    mk = lambda: make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    e1 = HSGD(model.loss, sgd(0.05), mk(), comms=Comms("topk", rate=0.25))
    s1 = e1.init(jax.random.PRNGKey(0), model.init)
    for t in range(16):
        s1, _ = e1.step(s1, batch_fn(t))
    e2 = HSGD(model.loss, sgd(0.05), mk(), comms=Comms("topk", rate=0.25))
    s2 = e2.init(jax.random.PRNGKey(0), model.init)
    s2, _ = e2.run_rounds(s2, batch_fn, 16)
    assert max_diff(s1.params, s2.params) == 0.0
    assert max_diff(s1.comms, s2.comms) == 0.0


def test_masked_step_with_comms(setup):
    """Runtime participation masks still work through the comms path, and a
    masked worker's error-feedback residual is not consumed (it transmitted
    nothing, even though it receives the aggregate per Algorithm 1)."""
    ds, model = setup
    topo = make_topology("uniform", spec=HierarchySpec((2, 4), (8, 4)))
    mask = np.array([1, 1, 0, 1, 1, 0, 1, 1], bool)
    for comms in (Comms(), Comms("topk", rate=0.1)):
        eng = HSGD(model.loss, sgd(0.05), topo, comms=comms)
        st = eng.init(jax.random.PRNGKey(0), model.init)
        for t in range(8):
            st, m = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                             mask=mask)
        assert np.isfinite(float(m["ce"]))
        if comms.codec.stateful:
            for r in jax.tree.leaves(st.comms):
                assert float(jnp.abs(r[~mask]).max()) == 0.0
                assert float(jnp.abs(r[mask]).max()) > 0.0
