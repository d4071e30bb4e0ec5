"""``segments_per_request.serve``: mean ``TenantMetrics.segments`` over
the requests that missed the cache and were not deduplicated (a solo
request is one segment; a coalesced one, one per superchunk)."""
import numpy as np


def read(run):
    miss = [r["serve"]["segments"] for r in run["record"].get(
        "requests", ()) if "serve" in r and not r["serve"]["cache_hit"]
        and not r["serve"]["deduped"]]
    if not miss:
        return None
    return float(np.mean(miss))
