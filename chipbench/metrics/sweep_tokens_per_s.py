"""Training tokens per second of the pool: batch x sequence of every step
retired in the window, across all jobs, over the window's seconds."""


def read(run):
    return run.tokens / run.window_s
