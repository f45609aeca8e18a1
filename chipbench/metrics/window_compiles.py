"""Programs compiled in the window (asked of the persistent cache and
not found there), from the program's counters under every segment."""
from harness import program


def read(run):
    return program.window_compiles()
