"""One run of one cell: set-up, the measured window, the traced segment,
and the comparison with the reference.

``run_cell`` is everything but the look for a chip, so the CPU tests drive
it at tiny sizes; ``run.py`` adds that look and prints the result.

Set-up (``setup_s``) makes the weights and the batch pool on the device in
one jitted call each, builds the engine, and drives its state through the
first global round with the window's own call, ``run_rounds``: one call up
to each average within the traffic's ``check_steps``, so the state can be
read after it, and one call for the rest of the round.  These calls use
(and the first run in a checkout compiles) the cell's two round programs,
the same ones the window runs; later runs find them in the persistent
cache.  The window then calls ``run_rounds(state, batch_fn, T=G)`` back to
back, each call finished once its history is on the host and
``block_until_ready(state)`` has returned, until ``seconds`` have passed.
With ``trace`` one call under the profiler is left out and ``trace_calls``
further calls make the traced segment; its trace is read once into the
device's busy time (``trace_reduce``) and its split by the program's named
scopes (``scopes``), both on the context the per-layer readers get.  After
both, the program's state is freed and the plain reference replays the
first ``check_steps`` steps.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import shutil
import statistics
import tempfile
import time
import types
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from chipbench import check, data, engine, peaks, reference, scopes, weights
from chipbench.registry import Cell, metric_reader
from chipbench.trace_reduce import find_xplane, reduce_events

SPANS = ("chipbench.run_rounds", "chipbench.block", "chipbench.history")
# the call that starts the profiler's segment: outside the traced window,
# which the reduction takes from the ``chipbench.*`` spans
WARM_SPANS = tuple(s.replace("chipbench.", "profiler_warm.") for s in SPANS)


class NoChip(RuntimeError):
    """The devices JAX found cannot hold the cell."""


def chips_for(cell: Cell, require_tpu: bool) -> List:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {devices[0].platform} "
                     f"devices {devices}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"sees {len(devices)}")
    return devices[:cell.chips]


def enable_cache() -> str:
    """The program's persistent compilation cache, with every program kept
    (the small set-up programs too) so that only a cell's first run in a
    checkout compiles.  Eviction is off: the cache belongs to this checkout,
    and an eviction-enabled cache takes a file lock (waiting up to 10 s) on
    every read and write."""
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


@dataclasses.dataclass
class Prepared:
    """An engine and the benchmark's generators for one cell; reusable
    across seeds."""
    cell: Cell
    model: dict          # the configuration's model sizes, as run
    eng: object
    n: int
    G: int
    check_steps: int     # steps the reference follows
    tokens_per_call: int
    make_params: Callable
    make_pool: Callable


def prepare(cell: Cell, model_overrides: Optional[dict] = None) -> Prepared:
    config = dict(cell.config)
    config["model"] = {**config["model"], **(model_overrides or {})}
    tr = cell.traffic
    eng, program_model = engine.build(config, tr)
    m = config["model"]
    make_params = weights.params_fn(cell.family, m)
    want = jax.eval_shape(program_model.init, jax.random.PRNGKey(0))
    made = jax.eval_shape(make_params, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(made) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(made))):
        raise ValueError("the benchmark's weights do not match the layout "
                         f"the program trains for {cell.name}")
    n = int(np.prod(tr["group_sizes"]))
    G = int(tr["periods"][0])
    check_steps = int(tr.get("check_steps", G))
    if not 0 < check_steps <= G or not reference.sync_level(
            check_steps - 1, tr["periods"]):
        raise ValueError(f"check_steps {check_steps} must end in an average "
                         f"within the first global round of {G} steps")
    return Prepared(cell, m, eng, n, G, check_steps,
                    G * n * tr["batch"] * tr["seq"],
                    make_params,
                    data.pool_fn(tr["pool_steps"], n, tr["batch"], tr["seq"],
                                 m["vocab_size"]))


def _keys(seed: int):
    k_w, k_d = jax.random.split(weights.seed_key(seed))
    return k_w, k_d


def _like(tree, state_params):
    """``tree`` placed as the state's params are (replicated on a mesh)."""
    sharding = jax.tree.leaves(state_params)[0].sharding
    if len(sharding.device_set) == 1:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(tree, NamedSharding(sharding.mesh, P()))


class Laps:
    """Seconds of named stages on ``time.perf_counter``'s clock: each call
    adds the time since the last one (or ``t0``) to its stage."""

    def __init__(self, t0: float):
        self.mark, self.seconds = t0, {}

    def __call__(self, name: str):
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.mark
        self.mark = now


def first_round(prep: Prepared, seed: int, finish: bool = True,
                lap: Optional[Laps] = None):
    """Build the state from ``seed`` and drive it through the first global
    round: one call up to each average within ``check_steps``, then (with
    ``finish``) one call for the rest.  Returns ``(state, pool, losses,
    readings)``, ``losses`` of the first ``check_steps`` steps and
    ``readings[step] = (delta, spread)`` after each of their averages.
    ``lap`` times the stages ``init``, ``round_calls`` and ``readings``."""
    lap = lap or Laps(time.perf_counter())
    k_w, k_d = _keys(seed)
    p0 = prep.make_params(k_w)
    pool = prep.make_pool(k_d)
    eng, tr = prep.eng, prep.cell.traffic
    state = eng.init(k_w, lambda _: p0)
    p0 = _like(p0, state.params)
    jax.block_until_ready((state, p0))
    batch_fn = _batch_fn(pool)
    losses, readings, t = [], {}, 0
    lap("init")
    for step in range(prep.check_steps):
        level = reference.sync_level(step, tr["periods"])
        if not level:
            continue
        state, hist = eng.run_rounds(state, batch_fn, step + 1 - t)
        jax.block_until_ready(state)
        losses += [r["ce"] for r in hist]
        lap("round_calls")
        leaders = reference.group_leaders(prep.n, tr["group_sizes"], level)
        readings[step + 1] = tuple(np.asarray(a) for a in jax.device_get(
            check.program_readings(state.params, p0, leaders)))
        lap("readings")
        t = step + 1
    if finish and t < prep.G:
        state, _ = eng.run_rounds(state, batch_fn, prep.G - t)
        jax.block_until_ready(state)
        lap("round_calls")
    return state, pool, losses, readings


def _batch_fn(pool):
    return lambda t: pool[t % len(pool)]


def reference_readings(prep: Prepared, seed: int, pool):
    """The reference's first ``check_steps`` steps from the same weights (in
    float32) and the batches the program took from the pool."""
    k_w, _ = _keys(seed)
    p0 = jax.tree.map(lambda x: x.astype(np.float32), prep.make_params(k_w))
    tr = prep.cell.traffic
    return reference.first_global_round(
        prep.cell.family.loss, prep.model, tr["group_sizes"], tr["periods"],
        tr["lr"], p0, [pool[t % len(pool)] for t in range(prep.check_steps)])


def drive(eng, state, batch_fn, G: int, until: float, min_calls: int = 1,
          spans=SPANS):
    """Global-round calls back to back until ``until`` (perf_counter) has
    passed; returns ``(state, latencies, failed, t_end)``."""
    lat, failed = [], 0
    call, block, history = spans
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(call):
            state, hist = eng.run_rounds(state, batch_fn, G)
        with jax.profiler.TraceAnnotation(block):
            jax.block_until_ready(state)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(history):
            failed += not all(math.isfinite(r["ce"]) for r in hist)
        lat.append(t1 - t0)
        if t1 >= until and len(lat) >= min_calls:
            return state, lat, failed, t1


class GcLog:
    """The collector's pauses while it listens: count, total and longest
    seconds of each generation's collections."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        return {f"gen{g}": [len(p), sum(p), max(p, default=0.0)]
                for g, p in self.pauses.items()}


def read_trace(path: str):
    """``(TraceSummary, ScopeSummary)`` of one ``.xplane.pb``, read once;
    each None where the trace holds no device operation."""
    events = scopes.read_events(path)
    return (reduce_events(e[:5] for e in events),
            scopes.reduce_scopes(events))


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def run_cell(root, cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, use_cache: bool = True,
             model_overrides: Optional[dict] = None,
             log: Callable[[str], None] = print) -> dict:
    """One run; returns the result object ``run.py`` prints.  ``t_start``
    is the process's start on ``time.perf_counter``'s clock."""
    stage = Laps(t_start)
    stage("start")
    devices = chips_for(cell, require_tpu)
    if use_cache:
        enable_cache()
    if trace:
        # the scopes live in the programs' debug info, which JAX leaves out
        # of the cache key unless told: without it a program cached by an
        # untraced run, or before the scopes existed, comes back without them
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    clog = engine.CompileLog()
    stage("devices")
    prep = prepare(cell, model_overrides)
    stage("prepare")
    state, pool, prog_losses, prog_readings = first_round(prep, seed,
                                                         lap=stage)
    batch_fn = _batch_fn(pool)
    # what set-up leaves behind is never garbage: keep it out of the
    # collections that the window's calls set off
    gc.collect()
    gc.freeze()
    stage("gc")
    setup_compiles = clog.snapshot()

    t_win = time.perf_counter()
    setup_s = t_win - t_start
    gclog = GcLog()
    state, lat, failed, t_end = drive(prep.eng, state, batch_fn, prep.G,
                                      t_win + seconds)
    window_s = t_end - t_win
    compiles = clog.snapshot()
    log_line = {"window_compiles": compiles[0] - setup_compiles[0],
                "window_traces": compiles[1] - setup_compiles[1],
                "setup_compiles": setup_compiles[0],
                "setup_compile_s": clog.backend_s,
                "window_calls": len(lat), "window_s": window_s,
                "call_s": [min(lat), statistics.median(lat), max(lat)],
                "window_gc": gclog.close()}
    summary, scope_summary, traced_calls = None, None, 0
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
        state, _, failed_w, _ = drive(prep.eng, state, batch_fn, prep.G, 0.0,
                                      spans=WARM_SPANS)
        state, lat_t, failed_t, _ = drive(
            prep.eng, state, batch_fn, prep.G, 0.0,
            min_calls=int(prep.cell.traffic["trace_calls"]))
        jax.profiler.stop_trace()
        failed += failed_w + failed_t
        traced_calls = len(lat_t)
        summary, scope_summary = read_trace(find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        log_line["traced_call_s"] = lat_t
        if scope_summary is not None:
            steps = traced_calls * prep.G
            log_line.update(
                scope_ms_per_step={k: 1e3 * v / steps for k, v
                                   in scope_summary.scope_s.items()},
                host_gap_s=scope_summary.host_gap_s,
                unscoped_ops=scope_summary.unscoped_ops)
    peak_bytes = memory_peak(devices)
    gc.unfreeze()
    del state
    prep.eng = None
    gc.collect()
    stage.mark, compile_s = time.perf_counter(), clog.backend_s
    ref = reference_readings(prep, seed, pool)
    correct, checks = check.compare(prog_losses, prog_readings, *ref,
                                    limits=cell.limits)
    stage("reference")
    log_line.update(stages=stage.seconds,
                    reference_compile_s=clog.backend_s - compile_s,
                    slowest_compiles=clog.slowest())
    log(log_line)

    tr = prep.cell.traffic
    e2e = {"tokens_per_s_per_chip": len(lat) * prep.tokens_per_call
           / window_s / cell.chips,
           "setup_s": setup_s}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    if not trace:
        metrics = {name: {"value": e2e[name], "unit": unit}
                   for name, unit in units.items()}
        breakdown = None
    else:
        ctx = types.SimpleNamespace(
            trace=summary, scopes=scope_summary, chips=cell.chips,
            device_kind=device["kind"],
            traced_tokens=traced_calls * prep.tokens_per_call,
            traced_steps=traced_calls * prep.G,
            scope_seconds=functools.partial(scopes.under, scope_summary),
            family=cell.family,
            flops_per_token=cell.family.flops_per_token(prep.model,
                                                        tr["seq"]),
            peaks=peaks, model=prep.model, traffic=tr)
        metrics, breakdown = {}, None
        for name, entry in cell.per_layer.items():
            value = metric_reader(root, name)(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": entry["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": [list(x) for x in summary.top_ops],
                         "idle_gaps": [list(x) for x in summary.idle_gaps]}
    result: Dict = {"correct": correct, "attempted": len(lat),
                    "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
