"""Chip-microseconds of leaf device time per trained token in the `head_loss`
scope (the final norm, the unembedding and the loss), from the trace,
attributed by `bench/scopes.py`."""
from bench import scopes


def read(run):
    return scopes.us_per_token(run, "head_loss")
