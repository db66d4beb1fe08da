"""Calling-context tree (CCT) over scoped op_name metadata.

HPCToolkit organizes a kernel's instructions into a CCT spanning device
functions, inlined templates, loops and statements (paper §III-B).  The XLA
analogue: JAX embeds the full traced call path in each HLO instruction's
``metadata op_name`` (e.g. ``jit(train_step)/while/body/decoder/layer/attn/
qk_matmul``) — model-library scopes play the role of source files, which is
what makes Kripke-style "the root cause is three framework layers away"
diagnoses possible (§VI-E).

The CCT aggregates per-instruction samples/stall cycles bottom-up so reports
can show per-layer / per-module hot paths.

A measured profile folds into the same scopes: `seconds_by_scope` maps a
device trace's per-instruction seconds onto the module's instructions and
sums them by `scope_of` each instruction's op_name.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Collection, Dict, List, Mapping, Optional, Tuple

from .isa import Instruction, Module
from .sampler import StallProfile


@dataclass
class CCTNode:
    name: str
    path: Tuple[str, ...]
    children: Dict[str, "CCTNode"] = field(default_factory=dict)
    instructions: List[str] = field(default_factory=list)  # qualified names
    stall_cycles: float = 0.0
    total_samples: float = 0.0

    def child(self, name: str) -> "CCTNode":
        if name not in self.children:
            self.children[name] = CCTNode(name=name, path=self.path + (name,))
        return self.children[name]

    def walk(self):
        yield self
        for c in self.children.values():
            yield from c.walk()

    def hot_path(self) -> List["CCTNode"]:
        """Descend along the highest-stall child at each level."""
        path = [self]
        node = self
        while node.children:
            node = max(node.children.values(), key=lambda c: c.stall_cycles)
            if node.stall_cycles <= 0:
                break
            path.append(node)
        return path


def build_cct(module: Module, profile: Optional[StallProfile] = None) -> CCTNode:
    root = CCTNode(name="<root>", path=())
    for instr in module.all_instructions():
        scope = instr.scope_path()
        node = root
        for part in scope:
            node = node.child(part)
        node.instructions.append(instr.qualified_name)
        if profile is not None:
            rec = profile.records.get(instr.qualified_name)
            if rec is not None:
                # accumulate up the path
                cur = root
                cur.stall_cycles += rec.latency_samples
                cur.total_samples += rec.total_samples
                for part in scope:
                    cur = cur.children[part]
                    cur.stall_cycles += rec.latency_samples
                    cur.total_samples += rec.total_samples
    return root


def format_hot_path(root: CCTNode, limit: int = 12) -> str:
    lines = []
    for i, node in enumerate(root.hot_path()[:limit]):
        pct = 100.0 * node.stall_cycles / max(root.stall_cycles, 1e-12)
        lines.append(f"{'  ' * i}{node.name or '<root>'}  "
                     f"[{node.stall_cycles:,.0f} stall cyc, {pct:.1f}%]")
    return "\n".join(lines)


# -- measured device time by named scope --------------------------------------

# Instructions whose device events enclose the events of the computations
# they run: counting them too would count their bodies twice.
ENCLOSING_OPCODES = frozenset({"while", "conditional", "call"})

# One op_name component with its transform wrappers stripped:
# "transpose(jvp(attn))" -> "attn", "jit(train_step)" -> "train_step".
_COMPONENT_RE = re.compile(r"^(?:[\w.\-]+\()*(?P<name>[^()]*?)\)*$")
# The instruction a profiler event names: the bare name on the CPU
# ("fusion.7"), the instruction's text on the TPU ("%fusion.7 = bf16[..]").
_EVENT_RE = re.compile(r"^\s*%?(?P<name>[^\s=%]+)")


def scope_of(op_name: str, scopes: Collection[str]) -> Optional[str]:
    """The innermost of `scopes` on an op_name path, or None.  Transform
    wrappers are stripped first, so with `head_loss` among the scopes
    `jit(train_step)/transpose(jvp(head_loss))/jit(log_softmax)/add_any`
    is in `head_loss`."""
    found = None
    for part in op_name.split("/"):
        m = _COMPONENT_RE.match(part)
        name = m.group("name") if m else part
        if name in scopes:
            found = name
    return found


def event_instruction(event_name: str) -> str:
    """Name of the HLO instruction a device-trace event stands for."""
    m = _EVENT_RE.match(event_name)
    return m.group("name") if m else event_name


class InstructionScopes:
    """The named scope of each instruction of `module`, one of `scopes` or
    None; call it with an instruction.

    An instruction takes `scope_of` its op_name.  A fusion whose own
    op_name names no scope (its root was traced outside them, like a
    residual add fused behind a matmul) takes the scope of its costliest
    inner instruction that has one.  An instruction the compiler made with
    no op_name at all (a layout copy, a wrapped reduction) takes the scope
    of its first operand that has one.  One still without a scope takes
    the scope its consumers all have, looking through consumers with no
    op_name (tuples, copies): the compiler hoists a loop's buffers out of
    it and names them after the enclosing call, as it does with the
    zero-filled cotangent buffers of a scan's backward."""

    def __init__(self, module: Module, scopes: Collection[str]):
        self.module = module
        self.scopes = frozenset(scopes)
        self._memo: Dict[str, Optional[str]] = {}
        self._users: Dict[str, Dict[str, List[Instruction]]] = {}

    def __call__(self, instr: Instruction) -> Optional[str]:
        key = instr.qualified_name
        if key not in self._memo:
            self._memo[key] = None  # operands form a DAG; guards bad text
            self._memo[key] = self._find(instr)
        return self._memo[key]

    def _own(self, instr: Instruction) -> Optional[str]:
        found = scope_of(instr.op_name, self.scopes)
        if found is None and instr.opcode == "fusion":
            inner = [(i.flops, i.raw_bytes_read,
                      scope_of(i.op_name, self.scopes))
                     for c in instr.called_computations
                     for i in self.module.computations[c].instructions]
            inner = [t for t in inner if t[2] is not None]
            found = max(inner, key=lambda t: t[:2])[2] if inner else None
        return found

    def _find(self, instr: Instruction) -> Optional[str]:
        found = self._own(instr)
        comp = self.module.computations[instr.computation]
        if found is None and not instr.op_name:
            for name in instr.operands:
                src = comp.get(name)
                found = src and self(src)
                if found:
                    break
        return found or self._consumers_scope(instr)

    def _consumers_scope(self, instr: Instruction) -> Optional[str]:
        users = self._users.get(instr.computation)
        if users is None:
            users = self._users[instr.computation] = {}
            for i in self.module.computations[instr.computation].instructions:
                for name in i.operands:
                    users.setdefault(name, []).append(i)
        found, seen = set(), {instr.name}
        todo = list(users.get(instr.name, ()))
        while todo:
            user = todo.pop()
            if user.name in seen:
                continue
            seen.add(user.name)
            own = self._own(user)
            if own:
                found.add(own)
            elif not user.op_name:
                todo.extend(users.get(user.name, ()))
        return found.pop() if len(found) == 1 else None


@dataclass
class ScopeSeconds:
    """Measured device seconds folded into named scopes."""

    by_scope: Dict[str, float] = field(default_factory=dict)
    unattributed: float = 0.0   # leaf instructions in no named scope
    enclosing: float = 0.0      # while/conditional/call events, not counted
    unmatched: float = 0.0      # events that name no instruction of the module

    @property
    def leaf(self) -> float:
        """Seconds of leaf instructions: the scopes' and the rest."""
        return sum(self.by_scope.values()) + self.unattributed


def seconds_by_scope(module: Module, op_seconds: Mapping[str, float],
                     scopes: Collection[str]) -> ScopeSeconds:
    """Fold device seconds keyed by trace event name (`event_instruction`)
    into the named `scopes` of `module`'s instructions
    (`InstructionScopes`).  Only leaf instructions count: the events of
    `ENCLOSING_OPCODES` cover their bodies' events, which are counted
    themselves."""
    by_name = {i.name: i for i in module.all_instructions()}
    scope = InstructionScopes(module, scopes)
    out = ScopeSeconds()
    for event, seconds in op_seconds.items():
        instr = by_name.get(event_instruction(event))
        if instr is None:
            out.unmatched += seconds
        elif instr.opcode in ENCLOSING_OPCODES:
            out.enclosing += seconds
        elif (found := scope(instr)):
            out.by_scope[found] = out.by_scope.get(found, 0.0) + seconds
        else:
            out.unattributed += seconds
    return out


def cost_by_scope(module: Module, scopes: Collection[str]
                  ) -> Dict[Optional[str], Tuple[float, float]]:
    """LEO's modelled (FLOPs, bytes read and written) of one run of
    `module` by named scope, None for the rest: the leaf instructions that
    `seconds_by_scope` counts, a while body's times its trip count."""
    scope = InstructionScopes(module, scopes)
    out: Dict[Optional[str], Tuple[float, float]] = {}

    def walk(comp: str, times: float) -> None:
        for i in module.computations[comp].instructions:
            if i.opcode in ENCLOSING_OPCODES:
                inner = times * (i.trip_count if i.opcode == "while" else 1)
                for callee in i.called_computations:
                    walk(callee, inner)
                continue
            flops, nbytes = out.get(scope(i), (0.0, 0.0))
            out[scope(i)] = (flops + times * i.flops,
                             nbytes + times * (i.bytes_read + i.bytes_written))
    walk(module.entry, 1.0)
    return out
