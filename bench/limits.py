"""Readings that the limits of a cell's ``correct`` comparison are set from.

    python3 bench/limits.py --workload <cell> --seeds 11,12,13 --seconds 3

For each seed, in one process: weights and images from the seed, the
engine warmed up, a short window of the cell's own traffic, and the same
sample of served requests that a run compares.  It prints, per seed and
for each number in ``harness.GAP_NUMBERS`` (whether or not the cell's
configuration compares it):

* ``program``: the number over the served logits' gaps from the
  reference (float32 at HIGHEST precision);
* ``control``: the same number for the control, the reference computed
  with three bf16 passes per contraction (the nearest precision below
  HIGHEST) in the program's place.

A number's lower reading is the largest ``program`` over the seeds; its
upper reading the smallest ``control``.  The benchmark's own runs do not
run the control."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402
from bench import spec as spec_lib  # noqa: E402


def readings(jax, cell, seed: int, seconds: float) -> dict:
    cfg = cell.config
    params, images, engine = harness.build(jax, cfg, cell.traffic, seed)
    win, _ = harness.drive(jax, engine, images, cell.traffic, seed, seconds,
                           False)
    got, jobs, unanswered = harness.collect(win, cfg, seed)
    del engine
    gc.collect()
    want = harness.reference_logits(cfg["layers"], params, images, jobs)
    ctrl = harness.reference_logits(cfg["layers"], params, images, jobs,
                                    "bf16x3")
    gaps = {"program": harness.rel_err(got, want),
            "control": harness.rel_err(ctrl, want)}
    return {"seed": seed, "compared": len(jobs), "unanswered": unanswered,
            **{f"{side}.{name}": float(fn(g))
               for side, g in gaps.items()
               for name, fn in harness.GAP_NUMBERS.items()}}


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/limits.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    cell = spec_lib.resolve(args.workload)
    try:
        harness.open_session(jax, cell, "tpu", ROOT)
    except harness.NoAccelerator as e:
        print(f"limits: {e}", file=sys.stderr)
        return 2
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(jax, cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": cell.name, "seeds": len(rows)}
    for name in harness.GAP_NUMBERS:
        summary[name] = {
            "lower": max(r[f"program.{name}"] for r in rows),
            "upper": min(r[f"control.{name}"] for r in rows),
            "limit": cell.config["check"]["limits"].get(name)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
