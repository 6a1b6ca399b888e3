"""H-SGD engine semantics (Algorithm 1 / D.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HSGD, EngineConfig, GroupedTopology, HierarchySpec,
                        UniformTopology, contiguous, local_sgd, two_level)
from repro.data import FederatedDataset, label_shard_partition, make_classification
from repro.models import SimpleConfig, SimpleModel
from repro.optim import adam, momentum, sgd

N_WORKERS = 8


@pytest.fixture(scope="module")
def setup():
    x, y = make_classification(0, num_classes=8, dim=16, per_class=40)
    parts = label_shard_partition(y, [[j] for j in range(8)])
    ds = FederatedDataset(x, y, parts)
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=24,
                                     num_classes=8))
    return ds, model


def run_T(model, ds, topology, T=16, lr=0.05, opt=None):
    eng = HSGD(model.loss, opt or sgd(lr), topology, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    for t in range(T):
        st, m = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
    return st, eng


def max_param_diff(a, b):
    d = jax.tree.map(lambda x, y: float(jnp.abs(x - y).max()), a, b)
    return max(jax.tree.leaves(d))


def test_n1_group_equals_local_sgd(setup):
    ds, model = setup
    st1, _ = run_T(model, ds, UniformTopology(two_level(N_WORKERS, 1, 8, 4)))
    st2, _ = run_T(model, ds, UniformTopology(local_sgd(N_WORKERS, 4)))
    assert max_param_diff(st1.params, st2.params) == 0.0


def test_i_equals_g_is_local_sgd_p_g(setup):
    ds, model = setup
    st1, _ = run_T(model, ds, UniformTopology(two_level(N_WORKERS, 2, 8, 8)))
    st2, _ = run_T(model, ds, UniformTopology(local_sgd(N_WORKERS, 8)))
    assert max_param_diff(st1.params, st2.params) < 1e-6


def test_uniform_equals_grouped(setup):
    ds, model = setup
    st1, _ = run_T(model, ds, UniformTopology(two_level(N_WORKERS, 2, 8, 4)))
    st2, _ = run_T(model, ds, GroupedTopology(contiguous(N_WORKERS, 2), G=8, I=4))
    assert max_param_diff(st1.params, st2.params) < 1e-5


def test_sync_sgd_replicas_identical(setup):
    ds, model = setup
    st, _ = run_T(model, ds, UniformTopology(two_level(N_WORKERS, 2, 1, 1)), T=5)
    d = jax.tree.map(lambda x: float(jnp.abs(x - x[0:1]).max()), st.params)
    assert max(jax.tree.leaves(d)) == 0.0


def test_replicas_diverge_between_syncs(setup):
    ds, model = setup
    st, _ = run_T(model, ds, UniformTopology(two_level(N_WORKERS, 2, 8, 4)), T=3)
    d = jax.tree.map(lambda x: float(jnp.abs(x - x[0:1]).max()), st.params)
    assert max(jax.tree.leaves(d)) > 1e-4  # non-IID shards => divergence


def test_group_members_equal_after_local_sync(setup):
    """After a local sync (t+1 = I), members of a group share params but
    groups differ (until the global sync)."""
    ds, model = setup
    topo = UniformTopology(two_level(N_WORKERS, 2, 8, 4))
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    for t in range(4):  # t+1=4 = I -> local sync
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
    w = st.params["h1"]["w"]  # (8, ...)
    g1, g2 = w[:4], w[4:]
    assert float(jnp.abs(g1 - g1[0:1]).max()) < 1e-6
    assert float(jnp.abs(g2 - g2[0:1]).max()) < 1e-6
    assert float(jnp.abs(g1[0] - g2[0]).max()) > 1e-5


def test_heterogeneous_local_periods(setup):
    """Theorem 1 allows different I_i per group; group with I=2 syncs at t+1=2
    while the other (I=4) does not."""
    ds, model = setup
    topo = GroupedTopology(contiguous(N_WORKERS, 2), G=8, I=(2, 4))
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    for t in range(2):
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
    w = st.params["h1"]["w"]
    assert float(jnp.abs(w[:4] - w[0:1]).max()) < 1e-6     # group 1 synced
    assert float(jnp.abs(w[4:] - w[4:5]).max()) > 1e-5     # group 2 did not


def test_three_level_subsumption(setup):
    """Algorithm D.1 break semantics: at t+1 = P_1 every level collapses to
    the global average; at t+1 = P_2 only the level-2 subtrees align."""
    ds, model = setup
    spec = HierarchySpec(group_sizes=(2, 2, 2), periods=(8, 4, 2))
    topo = UniformTopology(spec)
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    for t in range(4):  # t+1=4 = P_2
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
    w = st.params["h1"]["w"].reshape(2, 4, -1)
    for i in range(2):
        assert float(jnp.abs(w[i] - w[i, 0:1]).max()) < 1e-6
    assert float(jnp.abs(w[0, 0] - w[1, 0]).max()) > 1e-5
    for t in range(4, 8):  # t+1=8 = P_1: global
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
    w = st.params["h1"]["w"]
    assert float(jnp.abs(w - w[0:1]).max()) < 1e-6


def test_momentum_and_adam_states_aggregate(setup):
    ds, model = setup
    for opt in (momentum(0.05), adam(1e-2)):
        topo = UniformTopology(two_level(N_WORKERS, 2, 4, 2))
        eng = HSGD(model.loss, opt, topo, jit=True)
        st = eng.init(jax.random.PRNGKey(0), model.init)
        for t in range(4):
            st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)))
        m = st.opt_state["m"]["h1"]["w"]
        assert float(jnp.abs(m - m[0:1]).max()) < 1e-6  # t+1=4=G -> all equal


def test_loss_decreases_under_hsgd(setup):
    ds, model = setup
    topo = UniformTopology(two_level(N_WORKERS, 2, 8, 4))
    eng = HSGD(model.loss, sgd(0.1), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    gb = jax.tree.map(jnp.asarray, ds.global_batch(512))
    l0 = float(model.loss(eng.mean_params(st), gb)[0])
    for t in range(40):
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 16)))
    l1 = float(model.loss(eng.mean_params(st), gb)[0])
    assert l1 < l0 - 0.3, (l0, l1)


def test_partial_participation_semantics(setup):
    """Non-participants keep their params between syncs; at a sync they
    receive the participants' average (paper Appendix E semantics)."""
    import numpy as np
    from repro.core import sample_participation
    ds, model = setup
    topo = UniformTopology(two_level(N_WORKERS, 2, 8, 4))
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    mask = np.zeros(N_WORKERS, bool)
    mask[[0, 1, 4, 5]] = True   # 2 participants per group
    p_before = jax.tree.map(lambda x: x.copy(), st.params)
    # 3 pure-local steps: non-participants must not move at all
    for t in range(3):
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                         mask=mask)
    w = st.params["h1"]["w"]
    w0 = p_before["h1"]["w"]
    assert float(jnp.abs(w[2] - w0[2]).max()) == 0.0
    assert float(jnp.abs(w[3] - w0[3]).max()) == 0.0
    assert float(jnp.abs(w[0] - w0[0]).max()) > 1e-5
    # 4th step = local sync: every group member gets the participants' mean
    st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(3, 8)), mask=mask)
    w = st.params["h1"]["w"]
    assert float(jnp.abs(w[:4] - w[0:1]).max()) < 1e-6
    assert float(jnp.abs(w[4:] - w[4:5]).max()) < 1e-6


def test_participation_grouped_topology(setup):
    import numpy as np
    ds, model = setup
    topo = GroupedTopology(contiguous(N_WORKERS, 2), G=4, I=2)
    eng = HSGD(model.loss, sgd(0.05), topo, jit=True)
    st = eng.init(jax.random.PRNGKey(0), model.init)
    mask = np.array([True, True, False, False, True, False, True, False])
    for t in range(4):  # includes a local sync (t+1=2) and global (t+1=4)
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, 8)),
                         mask=mask)
    w = st.params["h1"]["w"]
    # after global sync everyone holds the same model
    assert float(jnp.abs(w - w[0:1]).max()) < 1e-6


def test_sample_participation_at_least_one_per_group():
    from repro.core import contiguous as contig, sample_participation
    g = contig(12, 3)
    for seed in range(5):
        m = sample_participation(g, 0.25, seed)
        for i in range(3):
            assert m[g.members(i)].sum() >= 1
    m2 = sample_participation((2, 4), 0.5, 0)
    assert m2.shape == (8,) and m2[:4].sum() >= 1 and m2[4:].sum() >= 1


def test_grad_accumulation_equals_large_batch(setup):
    """SGD is linear in the gradient: accum_steps=2 over a batch equals one
    step on the full batch, bitwise-ish."""
    ds, model = setup
    topo = UniformTopology(two_level(N_WORKERS, 2, 4, 2))
    e1 = HSGD(model.loss, sgd(0.05), topo, jit=True, accum_steps=1)
    e2 = HSGD(model.loss, sgd(0.05), topo, jit=True, accum_steps=2)
    s1 = e1.init(jax.random.PRNGKey(0), model.init)
    s2 = e2.init(jax.random.PRNGKey(0), model.init)
    for t in range(4):
        b = jax.tree.map(jnp.asarray, ds.batch(t, 8))
        s1, m1 = e1.step(s1, b)
        s2, m2 = e2.step(s2, b)
    assert max_param_diff(s1.params, s2.params) < 1e-6


# -- the seeded update: SGD's step size pulled back through the loss -------

def _tied_lm():
    """A 2-layer tied-embedding qwen2 at float32 compute."""
    import dataclasses
    from repro.configs import get_config, reduced
    from repro.models import build_model
    cfg = dataclasses.replace(reduced(get_config("qwen2-0.5b")),
                              dtype="float32", param_dtype="float32")
    assert cfg.tie_embeddings
    return cfg, build_model(cfg)


def _lm_batch(cfg, t, lead=()):
    k1, k2 = jax.random.split(jax.random.PRNGKey(100 + t))
    shape = lead + (4, 8)
    return {"tokens": jax.random.randint(k1, shape, 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, shape, 0, cfg.vocab_size)}


def _hand_sgd_step(model, opt, params, state, batch, accum):
    """params + opt.update(mean microbatch gradient), written out."""
    mbs = jax.tree.map(lambda x: x.reshape((accum, -1) + x.shape[1:]), batch)
    grads = [jax.grad(lambda p: model.loss(p, jax.tree.map(
        lambda x: x[i], mbs))[0])(params) for i in range(accum)]
    g = jax.tree.map(lambda *gs: sum(gs) / accum, *grads)
    upd, state = opt.update(g, state, params)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    return jax.tree.map(jnp.add, params, upd), state, norm


def _rel(a, b):
    num = sum(float(jnp.sum(jnp.square(x - y)))
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(float(jnp.sum(jnp.square(y))) for y in jax.tree.leaves(b))
    return (num / den) ** 0.5


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("lr", ["constant", "warmup"])
def test_seeded_update_equals_sgd_update_of_the_gradient(lr, accum):
    """With SGD the local step pulls -lr back through the loss; two steps
    of it equal params + sgd.update(grad) to float32 rounding, the
    schedule's counter advances, and grad_norm reads the gradient's norm."""
    from repro.optim import linear_warmup
    cfg, model = _tied_lm()
    opt = sgd(0.5 if lr == "constant" else linear_warmup(0.5, 4))
    eng = HSGD(model.loss, opt, UniformTopology(two_level(4, 2, 4, 2)),
               EngineConfig(metrics="on", accum_steps=accum))
    assert eng.update_path == "seeded"
    step = jax.jit(eng.local_update_fn())
    p0 = model.init(jax.random.PRNGKey(0))
    ps, ss, pr, sr = p0, opt.init(p0), p0, opt.init(p0)
    for t in range(2):
        batch = _lm_batch(cfg, t)
        ps, ss, m = step(ps, ss, batch)
        pr, sr, norm = _hand_sgd_step(model, opt, pr, sr, batch, accum)
        assert int(ss["step"]) == int(sr["step"]) == t + 1
        np.testing.assert_allclose(float(m["grad_norm"]), float(norm),
                                   rtol=1e-5)
    upd_s = jax.tree.map(jnp.subtract, ps, p0)
    upd_r = jax.tree.map(jnp.subtract, pr, p0)
    assert _rel(upd_s, upd_r) < 1e-5
    assert _rel(ps, pr) < 1e-6


@pytest.mark.parametrize("make", [lambda: momentum(0.1, beta=0.5),
                                  lambda: adam(1e-2)],
                         ids=["momentum", "adam"])
def test_nonlinear_optimizers_keep_the_gradient_path(make):
    """Momentum and Adam are not a scalar times the gradient: they report
    update="grad" and their local step is, bitwise, jax.grad then
    optimizer.update then the add."""
    cfg, model = _tied_lm()
    opt = make()
    assert opt.seed is None
    eng = HSGD(model.loss, opt, UniformTopology(two_level(4, 2, 4, 2)))
    assert eng.update_path == "grad"

    def by_hand(params, state, batch):
        grads, metrics = jax.grad(model.loss, has_aux=True)(params, batch)
        upd, state = opt.update(grads, state, params)
        return jax.tree.map(jnp.add, params, upd), state, metrics

    step, hand = jax.jit(eng.local_update_fn()), jax.jit(by_hand)
    p0 = model.init(jax.random.PRNGKey(0))
    pe, se, pr, sr = p0, opt.init(p0), p0, opt.init(p0)
    for t in range(2):
        pe, se, _ = step(pe, se, _lm_batch(cfg, t))
        pr, sr, _ = hand(pr, sr, _lm_batch(cfg, t))
    for a, b in zip(jax.tree.leaves((pe, se)), jax.tree.leaves((pr, sr))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seeded_update_has_no_multiply_over_the_tied_embedding():
    """The vmapped local step of a 2-layer tied qwen2, before XLA's passes:
    the gradient path scales the embedding's [n, V, D] gradient by -lr in a
    multiply of its own; the seeded path has none (the scale sits on the
    loss's cotangent)."""
    from repro.optim import Optimizer
    cfg, model = _tied_lm()
    n = 4
    emb = f"f32[{n},{cfg.vocab_size},{cfg.d_model}]"

    def multiplies(opt):
        eng = HSGD(model.loss, opt, UniformTopology(two_level(n, 2, 4, 2)))
        state = eng.init(jax.random.PRNGKey(0), model.init)
        text = jax.jit(jax.vmap(eng.local_update_fn())).lower(
            state.params, state.opt_state, _lm_batch(cfg, 0, (n,))
        ).as_text(dialect="hlo")
        return sum(1 for line in text.splitlines()
                   if " multiply(" in line
                   and line.split("=", 1)[1].lstrip().startswith(emb))

    opt = sgd(3e-3)
    assert multiplies(Optimizer(opt.init, opt.update)) == 1
    assert multiplies(opt) == 0
