"""A four-chip cell on the mesh executor, rehearsed on four virtual CPU
devices through the harness's inner function (the CLI refuses a non-TPU
device).  XLA_FLAGS must be set before JAX starts, hence the subprocess."""
import json
import os
import subprocess
import sys

from chipbench.tests.tiny import REPO

SCRIPT = """
import json, sys, time, pathlib
sys.path[:0] = [{src!r}, {repo!r}]
from chipbench import harness
from chipbench.registry import resolve
from chipbench.tests.tiny import make_root
root = make_root(pathlib.Path({tmp!r}), traffic={{"backend": "mesh"}},
                 chips=4)
cell = resolve(root, "tiny-dense.tiny")
r = harness.run_cell(root, cell, 9, 0.3, False, t_start=time.perf_counter(),
                     require_tpu=False, use_cache=False, log=lambda rec: None)
print(json.dumps(r))
"""


def test_mesh_cell_on_four_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(src=str(REPO / "src"), repo=str(REPO),
                         tmp=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
