"""Seconds of ``SaturnSession.profile`` in set-up (the Trial Runner)."""


def read(run):
    return run.profile_s
