"""``fetch_ms.sweep``: mean time of the ``finalize.fetch`` span (the
state's ``device_get`` at a sweep's end) per sweep of the window
(program spans, ``program_spans.py``)."""
from program_spans import roots, total


def read(run):
    got = roots(run)
    if got is None or "sweeps" not in run["record"]:
        return None
    _setup, window = got
    return 1e3 * total(window, "finalize.fetch") / len(window)
