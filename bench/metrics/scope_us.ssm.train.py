"""Chip-microseconds of leaf device time per trained token in the `ssm` scope
(the SSM mixer, the hybrid's SSM half included), from the trace, attributed
by `bench/scopes.py`."""
from bench import scopes


def read(run):
    return scopes.us_per_token(run, "ssm")
