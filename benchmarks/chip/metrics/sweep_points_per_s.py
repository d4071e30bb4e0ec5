"""``sweep_points_per_s``: design points of every whole ``explore()``
sweep of the window, over the time from the window's start to the end of
its last sweep (host clock)."""


def read(run):
    rec = run["record"]
    if "sweeps" not in rec:
        return None
    return rec["points"] / rec["span_s"]
