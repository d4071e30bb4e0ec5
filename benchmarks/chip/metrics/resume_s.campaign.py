"""``resume_s.campaign``: mean time of ``resume()`` per cycle (host
clock around the call)."""
import numpy as np


def read(run):
    rec = run["record"]
    if "cycles" not in rec:
        return None
    return float(np.mean([c["resume_s"] for c in rec["cycles"]]))
