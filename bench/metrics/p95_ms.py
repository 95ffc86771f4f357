"""95th percentile wall latency, in ms, of the requests due in the window:
from each request's due time to its logits being ready on the host."""
import numpy as np


def read(ctx):
    lat = [r.ready - r.due for r in ctx["window"].recs
           if r.status == "served"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
