"""95th percentile, over every window request, of its time per output token:
(finish - first token) / (tokens - 1), in ms. A failed request counts as never
served."""
import numpy as np


def read(run):
    t = [1e3 * (r.finish_s - r.first_token_s) / (len(r.generated) - 1)
         if r.ok else np.inf for r in run.records if r.new_tokens > 1]
    return float(np.percentile(t, 95)) if t else None
