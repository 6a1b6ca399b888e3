"""Cells, configurations, traffic, limits and metric readers are found by
name, and a new cell or metric needs only new files and entries."""
import filecmp
import json
import re
from pathlib import Path

import pytest

from chipbench import engine
from chipbench.registry import load_benchmark, metric_reader, resolve
from chipbench.tests.tiny import REPO, make_root

BENCH = load_benchmark(REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = resolve(REPO, workload)
    cfg = engine.model_config(cell.config)
    assert cfg.num_layers == cell.config["model"]["num_layers"]
    assert {"tokens_per_s_per_chip", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    assert set(cell.limits) == {"loss_rel", "update_gap", "sync_spread"}
    for name in cell.per_layer:
        assert callable(metric_reader(REPO, name))


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["reduced"] == \
            c["reduced"]


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    root = make_root(tmp_path)
    (root / "chipbench" / "metrics" / "test.calls.py").write_text(
        "def read(ctx):\n    return ctx.calls\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "test.calls", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "test",
        "moves": "tokens_per_s_per_chip", "workloads": ["tiny-ssd.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # every file the benchmark already had is there, unchanged
    for path in (REPO / "chipbench").rglob("*"):
        rel = path.relative_to(REPO)
        if path.is_file() and "tests" not in rel.parts \
                and "__pycache__" not in rel.parts:
            assert filecmp.cmp(path, root / rel, shallow=False), rel
    old = load_benchmark(REPO)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(old[key])] == old[key]
    cell = resolve(root, "tiny-ssd.tiny")
    assert cell.config["model"]["block_pattern"] == ["ssd"]
    assert cell.traffic["seq"] == 32 and "test.calls" in cell.per_layer
    assert "test.calls" not in resolve(root, "tiny-dense.tiny").per_layer
    read = metric_reader(root, "test.calls")
    assert read(type("Ctx", (), {"calls": 7})) == 7


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        resolve(REPO, "no-such-cell")
