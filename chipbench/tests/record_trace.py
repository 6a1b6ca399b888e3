"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python chipbench/tests/record_trace.py <out_dir>

On a TPU: three calls of a small jitted matmul inside a
``chipbench.run_rounds`` span, then a 20 ms sleep inside a
``chipbench.history`` span (the known idle gap), then three more calls.
Copies the ``.xplane.pb`` to ``<out_dir>/tiny.xplane.pb`` and prints each
plane's lines and the harness spans and device operations it holds.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench.trace_reduce import find_xplane, profile_events  # noqa: E402


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("chipbench.run_rounds"):
            y = f(f(f(x)))
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.history"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(find_xplane(tdir), out / "tiny.xplane.pb")
    lines = {}
    for plane, line, name, start, dur in profile_events(
            str(out / "tiny.xplane.pb")):
        lines.setdefault((plane, line), []).append((name, start, dur))
    for (plane, line), evs in sorted(lines.items()):
        print(plane, "|", line, "|", len(evs), "events")
        if plane.startswith("/device") or any(
                n.startswith("chipbench.") for n, _, _ in evs):
            for ev in evs[:8]:
                print("    ", ev)


if __name__ == "__main__":
    main(sys.argv[1])
