"""85th percentile, over every window request, of the seconds from when the
request was due to its first token. A failed request counts as never served.
The 85th is the highest percentile with about ten of the cell's 66 requests
beyond it; the 95th rests on three and swings with the seed's order."""
import numpy as np


def read(run):
    t = [r.first_token_s - r.arrival_s if r.ok else np.inf for r in run.records]
    return float(np.percentile(t, 85)) if t else None
