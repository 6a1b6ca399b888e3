"""Readings that set a cell's limits: sound runs, the control and the
planted faults, at the cell's own size, in one process.

    python chipbench/control.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults half_batch,sync_left_out] \
        [--out readings.jsonl]

For each seed it drives the program through the checked steps of the first
global round exactly as a run's set-up does (the rest of the round, which
no reference follows, is left out) and compares them with the plain
reference, printing
one JSON line of the three numbers (no limit applies).  The control is the
program with its parameters stored one precision lower than the
configuration states (``param_dtype`` bfloat16 for float32), the path a
later change could take to halve the state's memory.  Faults are those of
``faults.py``.  Needs a TPU unless ``--cpu``.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

LOWER = {"float32": "bfloat16"}


def readings(prep, seeds, refs, tag, out):
    from chipbench import check, harness
    no_limit = {k: float("inf") for k in check.NUMBERS}
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        state, pool, losses, prog = harness.first_round(prep, seed,
                                                        finish=False)
        del state
        gc.collect()
        key = (seed, prep.model["param_dtype"])
        if key not in refs:
            refs[key] = harness.reference_readings(prep, seed, pool)
        _, checks = check.compare(losses, prog, *refs[key], limits=no_limit)
        row = {"variant": tag, "seed": seed,
               **{k: c["value"] for k, c in checks.items()},
               "losses": losses, "ref_losses": refs[key][0],
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="/dev/null")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    for path in (str(ROOT / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from chipbench import faults, harness
    from chipbench.registry import resolve
    cell = resolve(ROOT, args.workload)
    harness.chips_for(cell, require_tpu=not args.cpu)
    if not args.cpu:
        harness.enable_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    refs = {}
    with open(args.out, "a") as out:
        readings(harness.prepare(cell), ints(args.seeds), refs, "sound", out)
        gc.collect()
        if args.control_seeds:
            dtype = cell.config["model"]["param_dtype"]
            prep = harness.prepare(cell, {"param_dtype": LOWER[dtype]})
            readings(prep, ints(args.control_seeds), refs, "control", out)
            del prep
            gc.collect()
        for name in filter(None, args.faults.split(",")):
            with faults.FAULTS[name]():
                prep = harness.prepare(cell)
                readings(prep, ints(args.fault_seeds or args.control_seeds),
                         refs, name, out)
            del prep
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
