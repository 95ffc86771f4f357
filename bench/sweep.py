"""Offered-rate sweep of an open-loop cell, to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 40,60,80

One process: set-up is paid once, then one window per rate at the cell's
configuration and mix with only ``rate_rps`` changed.  The knee is the
highest rate at which at least 99% of the requests due in the window were
answered inside it and the queue did not grow (``traffic.knee_rate``).  The cell's mix file then fixes its rate at about 0.8x
the knee.  Prints one line per rate and the knee as the last line."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402
from bench import spec as spec_lib  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    cell = spec_lib.resolve(args.workload)
    try:
        harness.open_session(jax, cell, "tpu", ROOT)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    _, images, engine = harness.build(jax, cell.config, cell.traffic,
                                      args.seed)
    print(f"set-up {time.perf_counter() - T_START:.3f}s", flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_rps=rate)
        win, _ = harness.drive(jax, engine, images, mix, args.seed,
                               args.seconds, False)
        lat = np.asarray([r.ready - r.due for r in win.recs
                          if r.status == "served"]) * 1e3
        row = {"rate_rps": rate, "offered": len(win.recs),
               "answered_in_window": win.served_in_window,
               "depth_first_half": win.depth_halves[0],
               "depth_second_half": win.depth_halves[1],
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "shed": sum(r.status == "shed" for r in win.recs)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = traffic.knee_rate(
        [r["rate_rps"] for r in rows], [r["answered_in_window"] for r in rows],
        [r["offered"] for r in rows],
        [r["depth_first_half"] for r in rows],
        [r["depth_second_half"] for r in rows])
    print(json.dumps({"workload": cell.name, "knee_rps": knee,
                      "rate_at_0.8_knee": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
