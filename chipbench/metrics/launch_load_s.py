"""Median over the window's segments of the seconds the program spent
tracing, lowering, loading and compiling programs (its ``compile.*``
counters under each segment)."""
from harness import program


def read(run):
    return program.launch_load_s()
