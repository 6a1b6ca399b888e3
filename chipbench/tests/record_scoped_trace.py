"""Record the small TPU trace of H-SGD rounds that ``test_scopes.py`` reads.

    python chipbench/tests/record_scoped_trace.py <out_dir>

On a TPU: a two-layer dense model of the ``tiny-dense`` test cell, four
workers in two groups (G=4, I=2) on the sim executor with the int8 wire
codec, divergence probes and a one-period stale level-2 sync, so that
every device scope of the round programs and every host span of
``run_rounds`` occurs.  Two ``run_rounds`` calls of one global round run
under the profiler inside ``chipbench.run_rounds`` spans.  Writes
``<out_dir>/scoped.xplane.pb``, cut to what the reduction reads (see
:func:`prune`; the whole trace is about 1 MB), and prints the device time
under each scope.
"""
import sys
import tempfile
from pathlib import Path

import jax

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]
from chipbench import data, engine, scopes  # noqa: E402
from chipbench.tests.tiny import TINY  # noqa: E402
from chipbench.trace_reduce import find_xplane, op_name  # noqa: E402


def build():
    from repro.core import HSGD, EngineConfig, HierarchySpec, make_topology
    from repro.models import build_model
    from repro.optim import sgd
    model = build_model(engine.model_config(TINY["tiny-dense"]))
    topo = make_topology("uniform", spec=HierarchySpec((2, 2), (4, 2)))
    eng = HSGD(model.loss, sgd(0.05), topo,
               EngineConfig(comms="int8", metrics="on", async_levels={2: 1}))
    return eng, model


def prune(space) -> None:
    """Keep what ``scopes.read_events`` reads: the devices' ``XLA Ops``
    and ``XLA Modules`` events with each operation's short name
    (``trace_reduce.op_name``) and ``tf_op``, and the host's
    ``chipbench.*`` and ``hsgd.*`` spans with their arguments.  The
    programs' ``Hlo Proto`` (``/host:metadata``) goes for its size, so an
    operation without a ``tf_op`` reads ``unscoped`` in the kept file."""
    space.DiscardUnknownFields()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        md = {e.key: e.value for e in plane.event_metadata}
        used, stats = set(), set()
        for line in plane.lines:
            keep = [ev for ev in line.events if device and line.name in (
                "XLA Ops", "XLA Modules") or not device and md[
                    ev.metadata_id].name.startswith(("chipbench.", "hsgd."))]
            for ev in keep:
                if device:
                    del ev.stats[:]
                stats |= {st.metadata_id for st in ev.stats}
                used.add(ev.metadata_id)
            del line.events[:]
            line.events.extend(keep)
        lines = [line for line in plane.lines if line.events]
        del plane.lines[:]
        plane.lines.extend(lines)
        entries = [e for e in plane.event_metadata if e.key in used]
        for e in entries:
            tf_op = [st for st in e.value.stats
                     if stat_names.get(st.metadata_id) == "tf_op"]
            stats |= {st.metadata_id for st in tf_op}
            del e.value.stats[:]
            e.value.stats.extend(tf_op)
            if device:
                e.value.name = op_name(e.value.name)
        del plane.event_metadata[:]
        plane.event_metadata.extend(entries)
        kept = [e for e in plane.stat_metadata if e.key in stats]
        del plane.stat_metadata[:]
        plane.stat_metadata.extend(kept)
    planes = [plane for plane in space.planes if plane.lines]
    del space.planes[:]
    space.planes.extend(planes)


def main(out_dir: str) -> None:
    eng, model = build()
    state = eng.init(jax.random.PRNGKey(0), model.init)
    pool = data.pool_fn(4, 4, 2, 32, TINY["tiny-dense"]["model"]
                        ["vocab_size"])(jax.random.PRNGKey(1))
    batch_fn = lambda t: pool[t % len(pool)]
    for _ in range(2):   # compile and warm every round program
        state, _ = eng.run_rounds(state, batch_fn, 4)
    jax.block_until_ready(state)
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("chipbench.run_rounds"):
            state, _ = eng.run_rounds(state, batch_fn, 4)
            jax.block_until_ready(state)
    jax.profiler.stop_trace()
    space = scopes.xspace_class()()
    space.ParseFromString(Path(find_xplane(tdir)).read_bytes())
    prune(space)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "scoped.xplane.pb").write_bytes(space.SerializeToString())
    s = scopes.reduce_scopes(scopes.read_events(str(out /
                                                    "scoped.xplane.pb")))
    print("bytes", (out / "scoped.xplane.pb").stat().st_size)
    if s is None:
        raise SystemExit("no device operation in the trace: not a TPU")
    print("busy_s", s.busy_s, "window_s", s.window_s, "calls", s.calls)
    for k, v in sorted(s.scope_s.items()):
        print(f"  {k:24s} {v:.9f}")
    print("host gaps", s.host_gap_s)
    for op, sec in s.unscoped_ops:
        print("  unscoped", sec, op[:200])


if __name__ == "__main__":
    main(sys.argv[1])
