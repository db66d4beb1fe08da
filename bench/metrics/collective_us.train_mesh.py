"""Chip-microseconds per trained token of device time in collective
operations (all-gather, all-reduce, reduce-scatter, all-to-all,
collective-permute, their asynchronous halves and the fusions named after
them; `bench/collectives.py`), summed over the chips, from the trace.
None where the trace holds no collective: a step on one chip."""
from bench import collectives


def read(run):
    s = collectives.seconds(run["trace"])
    if not s or not run["tokens"]:
        return None
    return 1e6 * s / run["tokens"]
