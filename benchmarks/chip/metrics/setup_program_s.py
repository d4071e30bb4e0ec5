"""``setup_program_s``: the total time of the set-up calls into the
program (the roots that started before the window's first); the rest of
``setup_s`` is process start, imports, JAX and TPU start-up and the
harness (program spans, ``program_spans.py``)."""
from program_spans import roots, seconds


def read(run):
    got = roots(run)
    if got is None or not got[0]:
        return None
    setup, _window = got
    return sum(seconds(r) for r in setup)
