"""`idle_share.train` of a cell laid over a mesh of chips: the same
reading, under an entry of its own so that the accepted entry's list of
cells stays as it is (`bench/metrics/idle_share.train.py`)."""
from bench import run as bench_run


def read(run):
    return bench_run.metric_reader("idle_share.train")(run)
