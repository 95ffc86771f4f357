"""The first chip's idle time in one traced window, split by the program's
own spans, with the wire codec's host ms per request and what one span
costs.

    python3 bench/idle_by_span.py --workload <cell> --seed <n> --seconds <s>

Run from the root of a checkout, on a machine with the cell's chips.  One
process: the cell's set-up as ``bench/run.py`` makes it, the span cost,
then one window, traced as a ``--trace 1`` run traces it.  The last line
of standard output is one JSON object; see ``bench/spans.py::split``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import spans  # noqa: E402

if __name__ == "__main__":
    sys.exit(spans.main(sys.argv[1:]))
