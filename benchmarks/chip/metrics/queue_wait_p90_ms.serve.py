"""``queue_wait_p90_ms.serve``: 90th percentile of the served requests'
``TenantMetrics.queue_wait_s`` (submit to the start of their batch)."""
import numpy as np


def read(run):
    rec = run["record"]
    waits = [r["serve"]["queue_wait_s"] for r in rec.get("requests", ())
             if "serve" in r]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 90))
