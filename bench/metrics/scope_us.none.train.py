"""Chip-microseconds of leaf device time per trained token in no named
scope (`bench/scopes.py`): work that escapes the scopes, such as the
layer scan's gradient buffers.  It grows when a change moves work out of
the scopes, where `scope_coverage.train` would also fall when a scope
merely gets faster."""
from bench import scopes


def read(run):
    found = scopes.attribution(run)
    if found is None or not run["tokens"]:
        return None
    return found.unattributed * 1e6 / run["tokens"]
