"""Plain reference of the CamJ energy model, kept with the benchmark.

A frozen copy of the scalar CamJ model (the object model, the delay
model, ``estimate_energy`` and the Ed-Gaze / Rhythmic use cases)
and of its lowering to flat per-structure coefficients (``plan.py``).
It imports nothing of the program under test.  Two entry points:

* :mod:`.scalar` -- one design point in float64 Python, the semantics
  every output is judged against;
* :mod:`.vector` -- a whole cartesian design space in plain
  ``jax.numpy`` broadcasts, blocked over the first axis, reduced to the
  same per-variant summaries and top-k a sweep returns.
"""
