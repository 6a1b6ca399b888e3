"""Four-chip cells on the mesh executor, rehearsed on four virtual CPU
devices through the harness's inner function (the CLI refuses a non-TPU
device): the tiny traffic sound, with each planted fault and with the
control (parameters stored in bfloat16), its readings under the state's
own sharding beside the replicated ones, and the hierarchy of the mesh
cell's traffic file at tiny sizes.  XLA_FLAGS must be set before JAX
starts, hence one subprocess for all of them.  And ``sync.collective_frac``
read from a hand-made trace summary."""
import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import faults
from chipbench.registry import metric_reader
from chipbench.tests.tiny import REPO

MESH_CELL = "mesh4.g50i5.b4x256"

SCRIPT = """
import contextlib, json, sys, time, pathlib
sys.path[:0] = [{src!r}, {repo!r}]
import jax, numpy as np
from chipbench import check, faults, harness
from chipbench.registry import resolve
from chipbench.tests.tiny import make_root
out = {{"readings": []}}
readings = check.program_readings

def beside_replicated(params, p0, leaders):
    # each reading under the state's sharding, beside the same reading of
    # the parameters replicated on every device, and the sharding the
    # compiled readings program takes
    got = jax.device_get(readings(params, p0, leaders))
    want = jax.device_get(check._readings(harness._like(params, params), p0,
                                          tuple(int(x) for x in leaders)))
    takes = jax.tree.leaves(check.readings_fn(params, leaders).lower(
        params, p0).compile().input_shardings[0][0])
    out["readings"].append({{
        "leaders": [int(x) for x in leaders],
        "equal": all(np.array_equal(a, b) and a.dtype == b.dtype == np.float32
                     for a, b in zip(got, want)),
        "state_sharding": len(takes) == len(jax.tree.leaves(params)) and all(
            s.is_equivalent_to(x.sharding, x.ndim)
            for s, x in zip(takes, jax.tree.leaves(params)))}})
    return got

for traffic, variants in json.loads({runs!r}):
    root = make_root(pathlib.Path({tmp!r}) / str(len(out)),
                     traffic=traffic, chips=4)
    cell = resolve(root, "tiny-dense.tiny")
    for variant in variants:
        kw = dict(model_overrides={{"param_dtype": "bfloat16"}}) \\
            if variant == "control" else {{}}
        spy = traffic["name"] == "tiny" and variant == "sound"
        check.program_readings = beside_replicated if spy else readings
        with faults.FAULTS.get(variant, contextlib.nullcontext)():
            r = harness.run_cell(root, cell, 9, 0.3, False,
                                 t_start=time.perf_counter(),
                                 require_tpu=False, use_cache=False,
                                 log=lambda rec: None, **kw)
        out[f"{{traffic['name']}}.{{variant}}"] = r
print(json.dumps(out))
"""


def _mesh_traffic(name):
    if name == "tiny":
        return {"name": name, "backend": "mesh"}
    tr = json.loads((REPO / "chipbench" / "traffic" / f"{name}.json")
                    .read_text())
    # the file's hierarchy, periods, checked steps and executor; tiny rows
    return dict(tr, name=name, batch=2, seq=32, pool_steps=8)


BROKEN = sorted(faults.FAULTS) + ["control"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    plan = [(_mesh_traffic("tiny"), ["sound"] + BROKEN),
            (_mesh_traffic(MESH_CELL), ["sound"])]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(src=str(REPO / "src"), repo=str(REPO),
                         tmp=str(tmp_path_factory.mktemp("mesh")),
                         runs=json.dumps(plan))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traffic", ["tiny", MESH_CELL])
def test_mesh_cell_on_four_virtual_devices(runs, traffic):
    r = runs[f"{traffic}.sound"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
    tr = _mesh_traffic(traffic)
    assert tr["backend"] == "mesh"
    if traffic == MESH_CELL:   # the global average is among the checked
        assert tr["check_steps"] == tr["periods"][0]


def test_readings_under_the_state_sharding_are_the_replicated_ones(runs):
    # the tiny traffic averages in pairs after step 2 and over all four
    # workers after step 4: one reading each
    seen = runs["readings"]
    assert [r["leaders"] for r in seen] == [[0, 0, 2, 2], [0, 0, 0, 0]]
    assert all(r["equal"] for r in seen), seen
    assert all(r["state_sharding"] for r in seen), seen


@pytest.mark.parametrize("variant", BROKEN)
def test_broken_mesh_path_is_not_correct(runs, variant):
    r = runs[f"tiny.{variant}"]
    assert not r["correct"], r["checks"]


def test_collective_frac_from_a_hand_made_summary():
    from chipbench.trace_reduce import TraceSummary
    read = metric_reader(REPO, "sync.collective_frac")
    busy = {f"/device:TPU:{i}": 2.0 for i in range(4)}
    coll = {"/device:TPU:0": 0.2, "/device:TPU:1": 0.4,
            "/device:TPU:2": 0.2, "/device:TPU:3": 0.0}
    t = TraceSummary(window_s=2.5, busy_s=2.0, busy_s_per_device=busy,
                     top_ops=[], idle_gaps=[], collective_s=coll)
    # per chip 0.1, 0.2, 0.1, 0 of its busy time
    assert read(types.SimpleNamespace(trace=t)) == pytest.approx(0.1)
    assert read(types.SimpleNamespace(trace=None)) is None
