"""The program's layer scopes in a traced window: device seconds by scope.

The program opens a ``jax.named_scope`` at each hot-path layer's work
(:mod:`repro.obs.scopes` holds the names).  The name reaches the
compiled step's HLO as a segment of each instruction's ``op_name``, and
a device op in the trace carries its instruction's name, so each op of
``ctx.window.op_s`` is put down to a scope by this rule:

1. its instruction's own ``op_name``; for a fusion, the ``op_name`` of
   the fused computation's root (of the fusion itself where that root
   has none);
2. an instruction with no ``op_name`` inherits one: a ``while`` from its
   ``get-tuple-element`` users, an op of a ``while``'s body or condition
   from the ``while``, any other from its single user;
3. otherwise the op is unscoped.

The split is also logged, on standard error, with the share of busy time
that is unscoped and the share in fusions whose fused instructions span
more than one scope.

A program that opens no scopes (an older one) gives no split: every
reader then returns nothing.  Nothing here loads JAX.
"""
from __future__ import annotations

import dataclasses
import re
import sys

import tracecut

try:
    from repro.obs.scopes import SCOPES, scope_of
except ImportError:          # a program that names no scopes
    SCOPES, scope_of = (), None

_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(calls|body|condition)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    comp: str
    op_name: str | None
    operands: list
    callees: dict            # calls / body / condition -> computation
    root: bool


def parse(hlo_text: str) -> dict[str, Instr]:
    """Every instruction of an HLO module, by name."""
    out: dict[str, Instr] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY )?%([\w.\-]+)", line)
            comp = m.group(1) if m else None
            continue
        m = tracecut._INSTR.match(line)
        if not m or comp is None:
            continue
        _, opcode, tail = tracecut._split_shape(m.group(2))
        args = _args(tail[len(opcode):])
        name = _OP_NAME.search(tail)
        out[m.group(1)] = Instr(
            name=m.group(1), opcode=opcode, comp=comp,
            op_name=name.group(1) if name else None,
            operands=_OPERAND.findall(args),
            callees={k: v for k, v in _CALLEE.findall(tail)},
            root=line.lstrip().startswith("ROOT "))
    return out


def _args(text: str) -> str:
    """The parenthesised operand list at the start of ``text``."""
    depth = 0
    for i, c in enumerate(text):
        depth += c == "("
        depth -= c == ")"
        if depth == 0:
            return text[1:i]
    return text


class Scopes:
    """Each instruction's scope under the rule in the module's doc."""

    def __init__(self, hlo_text: str):
        self.instrs = parse(hlo_text)
        self.users: dict[str, list[str]] = {}
        self.roots: dict[str, str] = {}
        self.loop_of: dict[str, str] = {}       # body/condition -> while
        self.members: dict[str, list[str]] = {}
        for ins in self.instrs.values():
            self.members.setdefault(ins.comp, []).append(ins.name)
            if ins.root:
                self.roots[ins.comp] = ins.name
            for o in ins.operands:
                self.users.setdefault(o, []).append(ins.name)
            if ins.opcode == "while":
                for key in ("body", "condition"):
                    if key in ins.callees:
                        self.loop_of[ins.callees[key]] = ins.name
        self._memo: dict[str, str | None] = {}

    def op_name(self, name: str) -> str | None:
        ins = self.instrs[name]
        if ins.opcode == "fusion" and "calls" in ins.callees:
            root = self.roots.get(ins.callees["calls"])
            if root is not None and self.instrs[root].op_name:
                return self.instrs[root].op_name
        return ins.op_name

    def scope(self, name: str) -> str | None:
        if name not in self._memo:
            self._memo[name] = None        # a cycle resolves to unscoped
            self._memo[name] = self._resolve(name)
        return self._memo[name]

    def _resolve(self, name: str) -> str | None:
        ins = self.instrs.get(name)
        if ins is None:
            return None
        op_name = self.op_name(name)
        if op_name:
            return scope_of(op_name)
        users = self.users.get(name, [])
        if ins.opcode == "while":
            found = [self.scope(u) for u in users
                     if self.instrs[u].opcode == "get-tuple-element"]
            found = [s for s in found if s]
            return max(set(found), key=found.count) if found else None
        if ins.comp in self.loop_of:
            return self.scope(self.loop_of[ins.comp])
        if len(users) == 1:
            return self.scope(users[0])
        return None

    def mixed(self, name: str) -> bool:
        """Whether a fusion's fused instructions span more than one scope."""
        ins = self.instrs.get(name)
        if ins is None or ins.opcode != "fusion":
            return False
        comp = ins.callees.get("calls")
        found = {scope_of(self.instrs[m].op_name)
                 for m in self.members.get(comp, ())}
        return len(found - {None}) > 1


def hlo_text_of(ctx) -> str | None:
    """The compiled step's HLO text, which the harness gives as
    ``ctx.hlo_text``."""
    return getattr(ctx, "hlo_text", None)


def split(ctx) -> dict | None:
    """Device seconds of the traced window by scope (``None`` for the
    unscoped), with ``"mixed"``: the seconds in fusions that span more
    than one scope.  Memoized on ``ctx``; ``None`` where the program
    opens no scopes."""
    memo = getattr(ctx, "_scope_split", False)
    if memo is not False:
        return memo
    out = None
    text = hlo_text_of(ctx) if scope_of is not None else None
    if text:
        sc = Scopes(text)
        out = {s: 0.0 for s in (*SCOPES, None, "mixed")}
        for name, sec in ctx.window.op_s.items():
            out[sc.scope(name)] += sec
            if sc.mixed(name):
                out["mixed"] += sec
        if not any(out[s] for s in SCOPES):
            out = None
    ctx._scope_split = out
    busy = ctx.window.busy_s
    if out is not None and busy > 0:
        print("scopes: " + " ".join(
            f"{s}_ms={1e3 * out[s] / max(ctx.epochs, 1) / chips(ctx):.3f}"
            for s in SCOPES)
            + f" unscoped_share={100 * out[None] / busy:.3f}%"
            f" mixed_fusion_share={100 * out['mixed'] / busy:.3f}%",
            file=sys.stderr, flush=True)
    return out


def chips(ctx) -> int:
    """The chips of the traced cell, over which the window's device
    seconds are summed."""
    cell = getattr(ctx, "cell", None)
    return cell["chips"] if cell else 1


def ms_per_epoch(ctx, *names) -> float | None:
    """Device milliseconds per epoch in the named scopes, averaged over
    the chips, or ``None`` where they hold no device time."""
    got = split(ctx)
    if got is None or ctx.epochs == 0:
        return None
    s = sum(got[n] for n in names)
    return 1e3 * s / ctx.epochs / chips(ctx) if s > 0 else None
