"""``finalize_ms.sweep``: mean of ``wall_s - compile_s - eval_s`` of the
window's sweeps: the winners' re-gather and the result's assembly."""
import numpy as np


def read(run):
    rec = run["record"]
    if "sweeps" not in rec:
        return None
    return 1e3 * float(np.mean([s["wall_s"] - s["compile_s"] - s["eval_s"]
                                for s in rec["sweeps"]]))
