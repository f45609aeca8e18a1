"""Median over the window's checkpoint saves of the seconds each spent
in the program's ``checkpoint.write`` span."""
from harness import program


def read(run):
    return program.checkpoint_phase("checkpoint.write")
