"""Share of the traced window in which no operation ran on the device, in %:
1 - (union of the device operations' intervals) / window, from the
profiler trace (`bench/trace.py`)."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
