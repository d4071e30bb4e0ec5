"""``collective_pct``: the collectives' device time over the devices'
busy time, both summed over the chips (device trace)."""


def read(run):
    tr = run["trace"]
    if tr is None or run["chips"] < 2 or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / (tr["busy_s"] * tr["n_devices"])
