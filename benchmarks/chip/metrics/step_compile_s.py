"""``step_compile_s``: ``compile_s`` of the set-up calls that compiled
the cell's step executables (host clock; includes the first host prep)."""


def read(run):
    return run["record"].get("step_compile_s")
