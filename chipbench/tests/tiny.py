"""A benchmark root with tiny cells of both families, for CPU tests."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "tiny-dense": {
        "registry": "qwen2-0.5b", "family": "qwen2",
        "model": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256, "qkv_bias": True,
                  "tie_embeddings": True, "mlp_variant": "swiglu",
                  "rope_theta": 1000000.0, "norm": "rmsnorm",
                  "norm_eps": 1e-6, "block_pattern": ["global"],
                  "dtype": "float32", "param_dtype": "float32",
                  "remat": False}},
    "tiny-ssd": {
        "registry": "mamba2-130m", "family": "mamba2",
        "model": {"num_layers": 2, "d_model": 32, "num_heads": 4,
                  "num_kv_heads": 4, "d_ff": 0, "vocab_size": 128,
                  "mlp_variant": "none", "block_pattern": ["ssd"],
                  "ssm_state": 16, "ssm_expand": 2, "ssm_head_dim": 16,
                  "ssm_conv_width": 4, "ssm_chunk": 8,
                  "tie_embeddings": True, "norm": "rmsnorm",
                  "norm_eps": 1e-6, "dtype": "float32",
                  "param_dtype": "float32", "remat": False}},
}

TRAFFIC = {"backend": "sim", "group_sizes": [2, 2], "periods": [4, 2],
           "optimizer": "sgd", "lr": 0.05, "comms": None, "batch": 2,
           "seq": 32, "pool_steps": 8, "trace_calls": 2}

LIMITS = {"loss_rel": 1e-4, "update_gap": 1e-3, "sync_spread": 1e-4}


def make_root(tmp: Path, traffic=None, chips: int = 1) -> Path:
    """A copy of the benchmark (``BENCHMARK.json`` and ``chipbench/``) with
    a tiny cell per family added as new files and entries."""
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tr = dict(TRAFFIC, **(traffic or {}))
    (root / "chipbench" / "traffic" / "tiny.json").write_text(json.dumps(tr))
    for name, cfg in TINY.items():
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps({"name": name, **cfg}))
        (root / "chipbench" / "limits" / f"{name}.tiny.json").write_text(
            json.dumps(LIMITS))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.tiny", "config": name,
                                   "traffic": "tiny", "chips": chips,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
