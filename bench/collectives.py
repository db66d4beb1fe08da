"""Device time in collective operations, from a reduced trace
(`bench/trace.py`).

An event counts as collective when the HLO instruction it names is one of
KINDS: by the instruction's name ("all-gather.3", "all-reduce-start.1",
and fusions XLA names after one, as "all-reduce-scatter-fusion.2"), by its
opcode in the instruction's text, which a TPU event carries
("%ar.1 = f32[8]{0} all-reduce(...)", "-start"/"-done" halves included),
or by the computation a fusion calls ("calls=%all-gather-fusion.4").  A
CPU event carries the bare name only.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_KIND = "|".join(KINDS)
_EVENT = re.compile(r"^\s*%?(?P<name>[^\s=%]+)(?:\s*=\s*(?P<text>.*))?$",
                    re.S)
_OPCODE = re.compile(r"\s(?P<op>[a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%?(?:" + _KIND + ")")


def is_collective(event_name: str) -> bool:
    m = _EVENT.match(event_name)
    if not m:
        return False
    if m.group("name").startswith(KINDS):
        return True
    text = m.group("text") or ""
    op = _OPCODE.search(" " + text)
    return bool(op and op.group("op").startswith(KINDS)) or \
        bool(_CALLS.search(text))


def seconds(trace: Optional[Dict]) -> float:
    """Device seconds in collective operations inside the window, summed
    over the devices (0 without a trace)."""
    if not trace:
        return 0.0
    return sum(s for name, s in trace["ops"].items() if is_collective(name))
