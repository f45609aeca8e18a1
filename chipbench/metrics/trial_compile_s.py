"""Seconds of the Trial Runner's ``trial.compile`` spans in set-up,
refused (out-of-memory) compiles included."""
from harness import program


def read(run):
    return program.trial_compile_s()
