"""The system under test, built the way ``repro.launch.train.main`` builds
it, and a counter of the compilations JAX reports."""
from __future__ import annotations

import dataclasses

import jax


def model_config(config: dict):
    """The program's ``ModelConfig``: its registry entry with the sizes of
    the configuration file's ``model`` section."""
    from repro.configs import get_config
    base = get_config(config["registry"])
    model = dict(config["model"])
    model["block_pattern"] = tuple(model["block_pattern"])
    unknown = set(model) - {f.name for f in dataclasses.fields(base)}
    if unknown:
        raise KeyError(f"not fields of ModelConfig: {sorted(unknown)}")
    return dataclasses.replace(base, **model)


def build(config: dict, traffic: dict):
    """``(engine, model)``: ``build_model``, a uniform topology of the
    traffic's hierarchy, SGD at its constant learning rate and the
    executor it names, as ``train.main`` assembles them."""
    from repro.comms import Comms
    from repro.core import HSGD, EngineConfig, HierarchySpec, make_topology
    from repro.models import build_model
    from repro.optim import sgd
    model = build_model(model_config(config))
    spec = HierarchySpec(tuple(traffic["group_sizes"]),
                         tuple(traffic["periods"]))
    if traffic["optimizer"] != "sgd":
        raise ValueError(f"optimizer {traffic['optimizer']!r} not supported")
    comms = Comms(traffic["comms"]) if traffic.get("comms") else None
    eng = HSGD(model.loss, sgd(traffic["lr"]),
               make_topology("uniform", spec=spec),
               EngineConfig(executor=traffic["backend"], comms=comms))
    return eng, model


class CompileLog:
    """Counts backend compilations (persistent-cache hits included) and
    jaxpr traces that JAX reports while it listens."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.backend = self.traces = 0
        self.backend_s = 0.0
        self.durations = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.BACKEND:
            self.backend += 1
            self.backend_s += duration
            self.durations.append((duration, str(kw.get("fun_name", "?"))))
        elif event == self.TRACE:
            self.traces += 1

    def slowest(self, k: int = 5):
        """The ``k`` longest backend compilations, ``[(name, seconds)]``."""
        return [(n, d) for d, n in sorted(self.durations, reverse=True)[:k]]

    def snapshot(self):
        return self.backend, self.traces
