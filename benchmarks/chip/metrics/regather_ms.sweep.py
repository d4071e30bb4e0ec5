"""``regather_ms.sweep``: mean time of the ``finalize.regather`` span
(the winners' full rows through ``evaluate_bank``) per sweep of the
window (program spans, ``program_spans.py``)."""
from program_spans import roots, total


def read(run):
    got = roots(run)
    if got is None or "sweeps" not in run["record"]:
        return None
    _setup, window = got
    return 1e3 * total(window, "finalize.regather") / len(window)
