"""``prep_ms.sweep``: mean ``compile_s`` of the window's sweeps, whose
step is cached: lowering, the plan bank and the executable look-up."""
import numpy as np


def read(run):
    rec = run["record"]
    if "sweeps" not in rec:
        return None
    return 1e3 * float(np.mean([s["compile_s"] for s in rec["sweeps"]]))
