"""Median over each segment's steps after its first of the host time
between one step's sync and the next dispatch's return: the program's
``step.data``, ``step.place`` and ``step.dispatch`` spans."""
from harness import program


def read(run):
    return program.step_feed_s()
