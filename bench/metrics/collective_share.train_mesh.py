"""Share of the chips' busy time spent in collective operations, in %:
the collective device seconds of `collective_us.train_mesh` over the busy
chip-seconds of the window (each chip's busy union, summed over chips).
None where the trace holds no collective: a step on one chip."""
from bench import collectives


def read(run):
    trace = run["trace"]
    s = collectives.seconds(trace)
    if not s or not trace["busy_s"]:
        return None
    return 100.0 * s / (trace["busy_s"] * trace["devices"])
