"""``dispatch_host_us.sweep``: the host's time per dispatch in the
window's sweeps: the ``sweep.dispatch`` spans less their child spans
(the pacing waits, the final drain), over the ``sweep.dispatches``
counters (program spans, ``program_spans.py``)."""
from program_spans import named, roots, self_seconds


def read(run):
    got = roots(run)
    if got is None or "sweeps" not in run["record"]:
        return None
    _setup, window = got
    n = sum(r["counters"].get("sweep.dispatches", 0) for r in window)
    if not n:
        return None
    host_s = sum(self_seconds(r, s) for r in window
                 for s in named(r, "sweep.dispatch"))
    return 1e6 * host_s / n
