"""``megakernel_ns_per_point``: the megakernel's device time, summed over
the chips, per design point scored in the traced window."""


def read(run):
    tr, rec = run["trace"], run["record"]
    if tr is None or "sweeps" not in rec or not tr["kernel_s"]:
        return None
    return 1e9 * tr["kernel_s"] / rec["points"]
