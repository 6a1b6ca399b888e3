"""Where the device time of a cell's traced segment goes, by the program's
named scopes.

    python chipbench/scope_report.py --workload <name> --seed <n>

from the root of a checkout, on the chip the cell asks for.  Set-up as
``run.py`` makes it (without the timed window), then the traced segment of
``harness.run_cell``: one call left out under the profiler, then the
traffic's ``trace_calls`` calls inside the harness's spans.  The last line
of standard output is one JSON object: the device, the traced calls'
seconds, ``trace_reduce``'s busy and window seconds, the seconds under
each scope and the local step's and the syncs' milliseconds per step
(``scopes.per_step``), the idle seconds by host span, and the largest
unscoped operations.  The compile cache is keyed with the programs'
debug info here, so a program cached before its scopes is compiled anew.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def report(root, workload: str, seed: int,
           require_tpu: bool = True) -> dict:
    """The report of one cell at checkout ``root`` (``scopes`` None where
    the trace holds no device operation, as on a host-only backend)."""
    import jax
    from chipbench import harness, scopes
    from chipbench.registry import resolve
    from chipbench.trace_reduce import (find_xplane, profile_events,
                                        reduce_events)
    cell = resolve(root, workload)
    devices = harness.chips_for(cell, require_tpu)
    harness.enable_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    prep = harness.prepare(cell)
    state, pool, _, _ = harness.first_round(prep, seed)
    batch_fn = harness._batch_fn(pool)
    setup_s = time.perf_counter() - T_START
    calls = int(cell.traffic["trace_calls"])
    tdir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    jax.profiler.start_trace(tdir)
    state, _, _, _ = harness.drive(prep.eng, state, batch_fn, prep.G, 0.0,
                                   spans=harness.WARM_SPANS)
    state, lat, failed, _ = harness.drive(prep.eng, state, batch_fn, prep.G,
                                          0.0, min_calls=calls)
    jax.profiler.stop_trace()
    path = find_xplane(tdir)
    summary = reduce_events(profile_events(path))
    events = scopes.read_events(path)
    shutil.rmtree(tdir, ignore_errors=True)
    s = scopes.reduce_scopes(events)
    steps = len(lat) * prep.G
    out = {"workload": workload, "seed": seed, "setup_s": setup_s,
           "device": {"kind": devices[0].device_kind,
                      "count": len(devices)},
           "traced_calls": len(lat), "traced_steps": steps,
           "traced_call_s": lat, "failed": failed}
    if s is None:
        return {**out, "scopes": None}
    out.update(
        busy_s=summary.busy_s, window_s=summary.window_s, scope_s=s.scope_s,
        per_step=scopes.per_step(s, steps),
        scope_ms_per_step={k: 1e3 * v / steps
                           for k, v in s.scope_s.items()},
        host_gap_s=s.host_gap_s, calls=s.calls,
        unscoped_ops=s.unscoped_ops)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    for path in (str(ROOT / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from chipbench import harness
    try:
        out = report(ROOT, args.workload, args.seed)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
