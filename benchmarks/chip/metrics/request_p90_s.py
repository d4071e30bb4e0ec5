"""``request_p90_s``: 90th percentile over every request due in the
window of due time to final result; a failed or refused request counts
as the whole grace period (host clock)."""
import numpy as np


def read(run):
    rec = run["record"]
    if "latency_s" not in rec:
        return None
    return float(np.percentile(rec["latency_s"], 90))
