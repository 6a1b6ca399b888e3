"""Model code by family, found by name: the two families give what the
benchmark gave before it was split by family (weights bitwise, reference
losses and gradient norms bitwise, FLOPs exactly), and a new family enters
as new files only."""
import filecmp
import hashlib
import json
import time

import jax
import numpy as np
import pytest

from chipbench import data, harness, reference, weights
from chipbench.registry import family, load_benchmark, resolve
from chipbench.tests.tiny import REPO, TINY, TRAFFIC, make_root


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


# read from the benchmark before the split, at seed 2**31 + 7 (weights) and
# 11 (pool), on the CPU
PINNED = {
    "tiny-dense": {
        "params": "9490eb5e974931282c094dd2f2e55534c76a9d9dfec3609a90a0d78d9a96df45",
        "losses": ["0x1.635f0f8000000p+2", "0x1.6350080000000p+2",
                   "0x1.64b7960000000p+2", "0x1.6467030000000p+2"],
        "grad0": "c276390758dec201d5282650818d1f8cdc1272b1fda1d90f264e4febb637baf5",
        "flops": {32: 589824, 256: 933888}},
    "tiny-ssd": {
        "params": "24980e4543042ac8d601f0600dca5338c65275e64911e123e4c120b81f71a9e7",
        "losses": ["0x1.37fe810000000p+2", "0x1.3661208000000p+2",
                   "0x1.36b78d0000000p+2", "0x1.354f6e0000000p+2"],
        "grad0": "f856ed9739df8121862a530b438323a879b53c0404dc565cc77619287e1ece90",
        "flops": {32: 148992, 256: 148992}},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_family_gives_the_pinned_weights_losses_and_flops(name):
    m, fam = TINY[name]["model"], family(REPO, TINY[name]["family"])
    params = weights.params_fn(fam, m)(weights.seed_key(2**31 + 7))
    leaves = jax.tree_util.tree_leaves_with_path(params)
    h = hashlib.sha256()
    for path, x in leaves:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == PINNED[name]["params"]
    pool = data.pool_fn(4, 4, 2, 32, m["vocab_size"])(weights.seed_key(11))
    losses, grad0, _ = reference.first_global_round(
        fam.loss, m, TRAFFIC["group_sizes"], TRAFFIC["periods"],
        TRAFFIC["lr"], params, list(pool))
    assert [float(x).hex() for x in losses] == PINNED[name]["losses"]
    assert _digest([grad0]) == PINNED[name]["grad0"]
    for seq, want in PINNED[name]["flops"].items():
        assert fam.flops_per_token(m, seq) == want


@pytest.mark.parametrize("workload,want", [
    ("qwen2-0.5b.sim.g50i5.b1x256", 2476572672),
    ("mamba2-130m.sim.g50i5.b1x2048", 891297792)])
def test_cells_count_the_pinned_flops(workload, want):
    cell = resolve(REPO, workload)
    assert cell.family.flops_per_token(cell.config["model"],
                                       cell.traffic["seq"]) == want


# A family the benchmark does not have: a global attention block, then an
# SSD block, repeated, with an untied head; its layers are the two families'
HYBRID = '''"""A test family: (global attention, SSD) repeated, untied head."""
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench.flops import training_flops
from chipbench.reference import rmsnorm
from chipbench.registry import family
from chipbench.weights import cast_params, normal

ROOT = Path(__file__).resolve().parents[2]
qwen2, mamba2 = family(ROOT, "qwen2"), family(ROOT, "mamba2")


def make_params(m, key):
    assert tuple(m["block_pattern"]) == ("global", "ssd")
    assert not m["tie_embeddings"]
    d, v, units = m["d_model"], m["vocab_size"], m["num_layers"] // 2
    k_emb, k_head, k_att, k_ssd = jax.random.split(key, 4)
    tree = {"embed": normal(k_emb, (v, d), 0.02),
            "lm_head": normal(k_head, (d, v), 1 / math.sqrt(d)),
            "final_norm": {"scale": jnp.ones((d,))},
            "units": (qwen2._dense_block(k_att, m, units),
                      mamba2._ssd_block(k_ssd, m, units)),
            "rem": ()}
    return cast_params(tree, m["param_dtype"])


def loss(m, params, batch):
    def unit(x, p):
        return mamba2.ssd_layer(m, p[1], qwen2.dense_layer(m, p[0], x)), None
    x = params["embed"][batch["tokens"]]
    x, _ = jax.lax.scan(jax.checkpoint(unit), x, params["units"])
    logits = rmsnorm(x, params["final_norm"]["scale"],
                     m["norm_eps"]) @ params["lm_head"]
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def flops_per_token(m, seq):
    unit = qwen2._dense_layer_macs(m, seq) + mamba2._ssd_layer_macs(m, seq)
    return training_flops(m["num_layers"] // 2 * unit
                          + m["d_model"] * m["vocab_size"])
'''


def test_a_new_family_is_files_and_entries(tmp_path):
    root = make_root(tmp_path)
    base = root / "chipbench"
    (base / "families" / "hybrid.py").write_text(HYBRID)
    model = {**TINY["tiny-dense"]["model"],
             **{k: v for k, v in TINY["tiny-ssd"]["model"].items()
                if k.startswith("ssm_")},
             "block_pattern": ["global", "ssd"], "tie_embeddings": False}
    (base / "configs" / "tiny-hybrid.json").write_text(json.dumps({
        "name": "tiny-hybrid", "registry": "qwen2-0.5b", "family": "hybrid",
        "model": model}))
    (base / "limits" / "tiny-hybrid.tiny.json").write_text(
        (base / "limits" / "tiny-dense.tiny.json").read_text())
    bench = load_benchmark(root)
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": "chipbench/configs/tiny-hybrid.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-hybrid.tiny",
                               "config": "tiny-hybrid", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # every file the benchmark had is there, unchanged
    for path in (REPO / "chipbench").rglob("*"):
        rel = path.relative_to(REPO)
        if path.is_file() and "tests" not in rel.parts \
                and "__pycache__" not in rel.parts:
            assert filecmp.cmp(path, root / rel, shallow=False), rel
    cell = resolve(root, "tiny-hybrid.tiny")
    assert cell.family.__name__ == "chipbench_family_hybrid"
    r = harness.run_cell(root, cell, 2**32 + 5, 0.2, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         use_cache=False, log=lambda rec: None)
    assert r["correct"], r["checks"]
    # the untied head is a leaf of the tree the program trains
    prep = harness.prepare(cell)
    made = jax.eval_shape(prep.make_params, jax.random.PRNGKey(0))
    assert made["lm_head"].shape == (model["d_model"], model["vocab_size"])
