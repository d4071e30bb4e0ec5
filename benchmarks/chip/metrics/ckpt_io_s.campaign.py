"""``ckpt_io_s.campaign``: mean checkpoint-writer time per cycle, the
resume's ``io_s`` (campaign report)."""
import numpy as np


def read(run):
    rec = run["record"]
    if "cycles" not in rec:
        return None
    return float(np.mean([c["io_s"] for c in rec["cycles"]]))
