"""Engine queueing, in ms: the 95th percentile over the window's requests
of (wall start of the step() that dispatched a request - its due time)."""
import numpy as np


def read(ctx):
    wait = [r.step_start - r.due for r in ctx["window"].recs
            if r.status == "served"]
    return float(np.percentile(wait, 95)) * 1e3 if wait else None
