"""Chip-microseconds of leaf device time per trained token in the `mlp` scope
(the dense FFN), from the trace, attributed by `bench/scopes.py`."""
from bench import scopes


def read(run):
    return scopes.us_per_token(run, "mlp")
