"""``campaign_points_per_s``: points of every whole kill-and-resume cycle
of the window, over the time from the window's start to the end of its
last resume (host clock)."""


def read(run):
    rec = run["record"]
    if "cycles" not in rec:
        return None
    return rec["points"] / rec["span_s"]
