"""The names the profiler's trace of a real run carries (``repro.obs.spans``):
every device scope in the lowered round programs of both executors, and
``run_rounds``'s host spans in order with their arguments, adding no
device-to-host transfer."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.obs
from repro.core import HSGD, EngineConfig, HierarchySpec, make_topology
from repro.core.hsgd import compile_schedule
from repro.models.simple import SimpleConfig, SimpleModel
from repro.optim.optimizers import sgd

SPEC = HierarchySpec((2, 2), (4, 2))
LOCAL = ("local/while", "local/fwd", "transpose(jvp(local/fwd))",
         "local/update")
SYNC = ("sync/L1", "sync/L2", "sync/fold", "probe")
WIRE = tuple(f"sync/L{lvl}/{part}" for lvl in (1, 2)
             for part in ("encode", "decode"))
MESH = WIRE + ("sync/L1/collective", "sync/L2/collective")

# the mesh half: four virtual CPU devices need XLA_FLAGS before JAX starts
MESH_SCRIPT = """
import json, sys
sys.path.insert(0, {tests!r})
from test_obs_spans import scope_text
print(json.dumps(scope_text("mesh")))
"""


def world(executor="sim", **config):
    model = SimpleModel(SimpleConfig(kind="mlp", input_dim=16, hidden=8,
                                     num_classes=4))
    eng = HSGD(model.loss, sgd(0.1), make_topology("uniform", spec=SPEC),
               EngineConfig(executor=executor, **config))
    return eng, eng.init(jax.random.PRNGKey(0), model.init)


def batch_fn(t):
    x = jax.random.normal(jax.random.PRNGKey(t), (4, 3, 16))
    return {"x": x, "y": jnp.zeros((4, 3), jnp.int32)}


def scope_text(executor: str) -> str:
    """The debug-info text of every round program of one global period of
    an int8, probes-on, one-stale-level-2 engine."""
    eng, state = world(executor, comms="int8", metrics="on",
                       async_levels={2: 1})
    rounds = set(compile_schedule(eng.topology.schedule(8),
                                  async_levels=eng.async_levels))
    return "".join(
        eng.round_fn(rnd).lower(state, tuple(batch_fn(i) for i in
                                             range(rnd.n_local)))
        .as_text(debug_info=True) for rnd in rounds)


def test_sim_round_programs_carry_every_scope():
    text = scope_text("sim")
    missing = [s for s in LOCAL + SYNC + WIRE if s not in text]
    assert not missing, missing
    # the in-array mean is no cross-device collective
    assert "/collective" not in text


def test_mesh_round_programs_carry_every_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(Path(repro.obs.__file__).parents[2])]
                   + sys.path))
    code = MESH_SCRIPT.format(tests=str(Path(__file__).parent))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    text = json.loads(proc.stdout.strip().splitlines()[-1])
    missing = [s for s in LOCAL + SYNC + MESH if s not in text]
    assert not missing, missing


class Recorder:
    """Stands in for ``repro.obs.span``: logs each span's entry and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append(("enter", name, args))

            def __exit__(self, *exc):
                rec.log.append(("exit", name, args))

        return Span()


@pytest.mark.parametrize("metrics", [None, "on"])
def test_run_rounds_host_spans_in_order(monkeypatch, metrics):
    eng, state = world(metrics=metrics)
    state, _ = eng.run_rounds(state, batch_fn, 4)   # compile outside
    rec, gets = Recorder(), []
    device_get = jax.device_get

    def counting_get(x):
        gets.append(1)
        return device_get(x)

    monkeypatch.setattr(repro.obs, "span", rec)
    monkeypatch.setattr(jax, "device_get", counting_get)
    state, hist = eng.run_rounds(state, batch_fn, 4)
    drain = [("hsgd.drain", {"call": 4})] if metrics else []
    assert [(n, a) for kind, n, a in rec.log if kind == "enter"] == [
        ("hsgd.run_rounds", {"call": 4, "update": "seeded"}),
        ("hsgd.schedule", {"call": 4}),
        ("hsgd.dispatch", {"call": 4, "n_local": 2, "level": 2}),
        ("hsgd.dispatch", {"call": 4, "n_local": 2, "level": 1}),
        *drain, ("hsgd.history", {"call": 4})]
    assert rec.log[-1] == ("exit", "hsgd.run_rounds",
                           {"call": 4, "update": "seeded"})
    # one transfer per round's metrics, one per drain: what the loop
    # did before it had spans
    assert len(gets) == 2 + len(drain)
    assert [r["t"] for r in hist] == [5, 6, 7, 8]


def test_span_names_are_declared():
    assert repro.obs.SPANS == ("hsgd.run_rounds", "hsgd.schedule",
                               "hsgd.dispatch", "hsgd.drain",
                               "hsgd.history")
    with pytest.raises(ValueError, match="unknown span"):
        repro.obs.span("hsgd.other")
    with repro.obs.span("hsgd.dispatch", call=0, n_local=1, level=0):
        pass
