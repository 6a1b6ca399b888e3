"""End-to-end behaviour tests: the paper's claims on live training runs
(CPU scale), plus the train/serve drivers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HSGD, GroupedTopology, HierarchySpec, UniformTopology,
                        group_iid, group_noniid, local_sgd, two_level)
from repro.data import FederatedDataset, label_shard_partition, make_classification
from repro.models import SimpleConfig, SimpleModel
from repro.optim import sgd

N = 8


@pytest.fixture(scope="module")
def world():
    x, y = make_classification(3, num_classes=8, dim=24, per_class=80,
                               spread=1.5)
    parts = label_shard_partition(y, [[j] for j in range(8)])
    ds = FederatedDataset(x, y, parts)
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=24, hidden=32,
                                     num_classes=8))
    return ds, model


def train(model, ds, topology, T, lr=0.08, seed=0, bs=10):
    eng = HSGD(model.loss, sgd(lr), topology, jit=True)
    st = eng.init(jax.random.PRNGKey(seed), model.init)
    for t in range(T):
        st, _ = eng.step(st, jax.tree.map(jnp.asarray, ds.batch(t, bs)))
    gb = jax.tree.map(jnp.asarray, ds.global_batch(640))
    wbar = eng.mean_params(st)
    return float(model.loss(wbar, gb)[0]), float(model.accuracy(wbar, gb))


def test_sandwich_behavior_live(world):
    """Fig 3a: H-SGD(G, I) ends between local SGD P=I and P=G.
    Averaged over seeds to tame SGD noise."""
    ds, model = world
    T, G, I = 48, 16, 4
    losses = {"PI": [], "H": [], "PG": []}
    for seed in range(3):
        losses["PI"].append(train(model, ds, UniformTopology(local_sgd(N, I)),
                                  T, seed=seed)[0])
        losses["H"].append(train(model, ds,
                                 UniformTopology(two_level(N, 2, G, I)),
                                 T, seed=seed)[0])
        losses["PG"].append(train(model, ds, UniformTopology(local_sgd(N, G)),
                                  T, seed=seed)[0])
    pi, h, pg = (np.mean(losses[k]) for k in ("PI", "H", "PG"))
    assert pi <= h + 0.02, (pi, h, pg)
    assert h <= pg + 0.02, (pi, h, pg)


def test_group_iid_beats_group_noniid():
    """Fig 3c: grouping with small upward divergence converges better.
    World: 4 classes over 8 workers so a label-balanced grouping exists."""
    x, y = make_classification(3, num_classes=4, dim=24, per_class=160,
                               spread=1.5)
    parts = label_shard_partition(y, [[j % 4] for j in range(8)])
    ds = FederatedDataset(x, y, parts)
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=24, hidden=32,
                                     num_classes=4))
    labels = ds.dominant_labels()
    T, G, I = 48, 16, 4
    diffs = []
    for seed in range(3):
        l_iid = train(model, ds,
                      GroupedTopology(group_iid(labels, 2), G=G, I=I),
                      T, seed=seed)[0]
        l_non = train(model, ds,
                      GroupedTopology(group_noniid(labels, 2), G=G, I=I),
                      T, seed=seed)[0]
        diffs.append(l_non - l_iid)
    assert np.mean(diffs) > -0.02, diffs


def test_train_driver_smoke(tmp_path):
    from repro.launch.train import main
    hist = main(["--arch", "qwen2-0.5b", "--reduced", "--workers", "4",
                 "--groups", "2", "--G", "4", "--I", "2", "--steps", "12",
                 "--batch", "2", "--seq", "32", "--log-every", "4",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "6"])
    assert hist[-1]["step"] == 12
    assert np.isfinite(hist[-1]["loss"])
    # loss decreases on the learnable synthetic stream
    assert hist[-1]["loss"] < hist[0]["loss"] + 0.05
    # resume from checkpoint
    hist2 = main(["--arch", "qwen2-0.5b", "--reduced", "--workers", "4",
                  "--groups", "2", "--G", "4", "--I", "2", "--steps", "14",
                  "--batch", "2", "--seq", "32", "--log-every", "2",
                  "--ckpt-dir", str(tmp_path)])
    assert hist2[-1]["step"] == 14


def test_serve_driver_smoke():
    from repro.launch.serve import main
    res = main(["--arch", "mamba2-130m", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])
    assert res.tokens.shape == (2, 4)


def test_multilevel_driver_smoke():
    from repro.launch.train import main
    hist = main(["--arch", "mamba2-130m", "--reduced",
                 "--levels", "2,2,2:8,4,2", "--steps", "8", "--batch", "2",
                 "--seq", "16", "--log-every", "8", "--comms", "int8"])
    assert hist[-1]["step"] == 8
    assert np.isfinite(hist[-1]["loss"])
    # comms on: cumulative wire accounting rides the telemetry records, and
    # 8 steps of (8,4,2) hit L3 twice, L2 once, L1 once
    assert hist[-1]["wire_cum_bytes"] > 0


def test_compile_cache_defers_to_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own handling stands."""
    from repro.launch.cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_repo_dir(monkeypatch):
    """Unset, the cache is <repo>/.jax_cache — never a temporary path."""
    from pathlib import Path
    from repro.launch.cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        repo = Path(__file__).resolve().parents[1]
        assert Path(path) == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("where", ["cpu_only", "script_alone"])
def test_chip_smoke_refuses_without_tpu(where, tmp_path):
    """chip_smoke.py exits non-zero and prints no result on a CPU-only
    host, and in a directory holding nothing else of the repo."""
    import os
    import shutil
    import subprocess
    import sys
    from pathlib import Path
    script = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "chip_smoke: FAILED" in out.stderr
