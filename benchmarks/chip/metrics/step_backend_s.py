"""``step_backend_s``: the ``step.compile`` spans of the set-up calls:
the XLA and Mosaic backend compile of the step, or its load from the
persistent cache (program spans, ``program_spans.py``).  Prints each
compile charged inside the window, with the span it was charged to."""
from program_spans import named, print_window_compiles, roots, total


def read(run):
    got = roots(run)
    if got is None:
        return None
    setup, window = got
    print_window_compiles("step_backend_s", window)
    if not any(named(r, "step.compile") for r in setup):
        return None
    return total(setup, "step.compile")
