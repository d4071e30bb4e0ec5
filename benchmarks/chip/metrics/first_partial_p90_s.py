"""``first_partial_p90_s``: 90th percentile over every request due in the
window of due time to its first partial update (the final one where it
is the only one); a failed request counts as the grace period."""
import numpy as np


def read(run):
    rec = run["record"]
    if "first_s" not in rec:
        return None
    return float(np.percentile(rec["first_s"], 90))
