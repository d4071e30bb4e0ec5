"""``step_lower_s``: the ``step.lower`` spans of the set-up calls:
tracing the step and lowering it to MLIR, Pallas to Mosaic included
(program spans, ``program_spans.py``).  Prints each compile charged
inside the window, with the span it was charged to."""
from program_spans import named, print_window_compiles, roots, total


def read(run):
    got = roots(run)
    if got is None:
        return None
    setup, window = got
    print_window_compiles("step_lower_s", window)
    if not any(named(r, "step.lower") for r in setup):
        return None
    return total(setup, "step.lower")
