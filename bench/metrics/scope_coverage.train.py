"""Share of device busy time that the named scopes account for, in %:
seconds of leaf instructions with a scope (`bench/scopes.py`) over
`busy_s`.  A check of the attribution (at 90% or more it sees the step);
to follow work that escapes the scopes read `scope_us.none.train`, which
a faster scope does not move."""
from bench import scopes


def read(run):
    found = scopes.attribution(run)
    trace = run["trace"]
    if found is None or not trace["busy_s"]:
        return None
    return 100.0 * sum(found.by_scope.values()) / trace["busy_s"]
