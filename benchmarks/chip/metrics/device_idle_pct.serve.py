"""``device_idle_pct.serve``: the share of the traced window in which the devices ran
no operation (device trace, averaged over the chips)."""


def read(run):
    tr = run["trace"]
    if tr is None or "requests" not in run["record"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
