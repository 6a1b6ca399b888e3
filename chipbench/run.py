"""Run one cell of the on-chip H-SGD training benchmark.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), and last ``checks``: each number compared with
the reference beside its limit, also the last lines of standard error.
Without a TPU, with fewer chips than the cell needs, or without the
program's ``src/`` beside this directory, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the persistent compilation cache lives in the checkout, at a fixed path,
# whatever the machine sets: the program takes the directory given here
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program is missing ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    for path in (str(ROOT / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from chipbench import harness
    from chipbench.registry import resolve
    cell = resolve(ROOT, args.workload)
    try:
        result = harness.run_cell(
            ROOT, cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
            log=lambda rec: print(json.dumps(rec), flush=True))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
