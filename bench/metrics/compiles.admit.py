"""Executables built inside the window (compiled, or loaded from the
persistent cache), counted from JAX's backend-compile monitoring events."""


def read(run):
    return run.counters.get("compiles")
