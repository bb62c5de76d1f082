"""From a profiler trace and the compiled step's HLO to per-layer numbers.

Nothing here loads a TPU library: the HLO is text, and the trace is read
with ``jax.profiler.ProfileData`` only when a file is given.

* :func:`hlo_ops` classifies every instruction of the compiled step by
  what it does: ``agg`` (a gather or scatter, a fusion holding one, the
  index preparation XLA adds for them, and any op whose result has one
  row per directed edge: the messages, the sort of the edge indices),
  ``matmul`` (dot or convolution), ``kernel`` (a Pallas call, with its
  kernel kind and its least FLOPs and bytes from :mod:`counts`) or
  ``other``.  Device ops in the trace carry the instruction's name.
* :func:`load_xplane` turns an ``.xplane.pb`` into plain event lists;
  :func:`reduce_window` sums them over the measured window.
"""
from __future__ import annotations

import base64
import dataclasses
import re

import counts

#: Pallas kernel names (the kernel function's name, or a ``name=`` given
#: to ``pallas_call``) by the fragment that identifies them, checked in
#: this order.
KERNEL_KINDS = (("dequant_matmul", "fused_bwd"),
                ("matmul_quant", "fused_fwd"),
                ("dequant_unpack", "dequant"),
                ("quant_pack", "quant"),
                ("rp_kernel", "rp"))

_AGG_TARGETS = ("AssumeGatherIndicesInBound", "GatherScatterIndicesBitpacked")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


@dataclasses.dataclass
class OpInfo:
    kind: str                      # agg | matmul | kernel | other
    kernel: str | None = None      # quant | dequant | fused_fwd | ...
    flops: int = 0
    bytes: int = 0


def _split_shape(rest: str) -> tuple[str, str, str]:
    """``rest`` of an instruction line -> (shape text, opcode, tail)."""
    depth, i = 0, 0
    while i < len(rest):
        c = rest[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    shape, tail = rest[:i], rest[i + 1:]
    m = re.match(r"([\w\-]+)\(", tail)
    return shape, (m.group(1) if m else ""), tail


def _braced(text: str, key: str) -> str:
    """The ``{...}`` value of ``key=`` in ``text`` (nested braces kept)."""
    i = text.find(key + "={")
    if i < 0:
        return ""
    i += len(key) + 1
    depth = 0
    for j in range(i, len(text)):
        depth += text[j] == "{"
        depth -= text[j] == "}"
        if depth == 0:
            return text[i + 1:j]
    return ""


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def _kernel_kind(tail: str) -> str | None:
    names = set(re.findall(r'op_name="([^"]*)"', tail))
    m = re.search(r'"body":"([A-Za-z0-9+/=]+)"', tail)
    if m:
        raw = base64.b64decode(m.group(1))
        names |= {s.decode() for s in
                  re.findall(rb"[A-Za-z_][A-Za-z0-9_]*kernel[A-Za-z0-9_]*",
                             raw)}
    for frag, kind in KERNEL_KINDS:
        if any(frag in n for n in names):
            return kind
    return None


def _edge_rows(shape: str, n_edges: int | None) -> bool:
    """Whether an array of ``shape`` has one row per directed edge (XLA
    pads index vectors to a multiple of 1024)."""
    if not n_edges:
        return False
    return any(dims and n_edges <= dims[0] < n_edges + 1024
               for _, dims in _shapes(shape))


def hlo_ops(hlo_text: str, n_edges: int | None = None
            ) -> dict[str, OpInfo]:
    """Every instruction of an optimized HLO module, by name;
    ``n_edges`` is the graph's directed edge count."""
    comps: dict[str, list[tuple[str, str, str, str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY )?%([\w.\-]+)", line)
            cur = m.group(1) if m else None
            comps[cur] = []
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            shape, op, tail = _split_shape(m.group(2))
            comps[cur].append((m.group(1), shape, op, tail))

    memo: dict[str, set] = {}

    def opcodes(comp: str) -> set:
        if comp not in memo:
            memo[comp] = set()
            acc = set()
            for _, _, op, tail in comps.get(comp, ()):
                acc.add(op)
                for callee in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                         tail):
                    acc |= opcodes(callee)
            memo[comp] = acc
        return memo[comp]

    out = {}
    for comp in comps:
        for name, shape, op, tail in comps[comp]:
            ops = {op}
            for callee in re.findall(r"calls=%([\w.\-]+)", tail):
                ops |= opcodes(callee)
            target = re.search(r'custom_call_target="([^"]+)"', tail)
            target = target.group(1) if target else ""
            if target == "tpu_custom_call":
                kk = _kernel_kind(tail)
                info = OpInfo("kernel", kk)
                if kk in ("quant", "dequant", "fused_fwd", "fused_bwd"):
                    info.flops, info.bytes = counts.kernel_cost(
                        kk, _shapes(_braced(tail,
                                            "operand_layout_constraints")),
                        _shapes(shape))
            elif (ops & {"gather", "scatter"} or target in _AGG_TARGETS
                  or _edge_rows(shape, n_edges)):
                info = OpInfo("agg")
            elif ops & {"dot", "convolution"}:
                info = OpInfo("matmul")
            else:
                info = OpInfo("other")
            out[name] = info
    return out


def module_name(hlo_text: str) -> str:
    m = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event, which the TPU
    profiler names by the instruction's whole text
    (``%fusion.10 = f32[...] fusion(...), ...``)."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def load_xplane(path: str) -> dict:
    """Plain events of a trace: ``device`` — per TPU plane, the ``XLA Ops``
    events as ``[name, start_ns, dur_ns, module]``; ``host`` — the host's
    events as ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    evs.append([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns),
                                str(stats.get("hlo_module", ""))])
            device[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    host.append([e.name, int(e.start_ns),
                                 int(e.duration_ns)])
    return {"device": device, "host": host}


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The ``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


@dataclasses.dataclass
class Window:
    """A traced window reduced: seconds and calls per HLO op, device busy
    time averaged over the chips, and the idle gaps by host activity."""

    window_s: float
    busy_s: float
    op_s: dict            # op name -> device seconds in the window
    op_calls: dict        # op name -> calls in the window
    gaps: list            # [(seconds, host activity)], longest first

    def seconds(self, ops: dict[str, OpInfo], pred) -> float:
        return sum(s for n, s in self.op_s.items()
                   if n in ops and pred(ops[n]))

    def kernel_totals(self, ops: dict[str, OpInfo], kinds, peaks: dict
                      ) -> tuple[float, float]:
        """(least seconds, device seconds) over all calls of kernels of
        ``kinds``; the least time of a call is the larger of its FLOPs
        over the peak rate and its bytes over the HBM bandwidth."""
        least = busy = 0.0
        for n, s in self.op_s.items():
            info = ops.get(n)
            if info is None or info.kernel not in kinds:
                continue
            per_call = max(info.flops / peaks["flops"],
                           info.bytes / peaks["hbm_bytes_per_s"])
            least += per_call * self.op_calls[n]
            busy += s
        return least, busy

    def top_ops(self, ops: dict[str, OpInfo], k: int = 10) -> list:
        def label(n):
            info = ops.get(n)
            tag = "?" if info is None else (info.kernel or info.kind)
            return f"{tag}:{n}"
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        return [[label(n), s] for n, s in top]


def self_ns(evs) -> list[int]:
    """Each event's own time: its duration less that of the events nested
    in it (a loop holds its body's ops on the same line).  ``evs`` sorted
    by start."""
    own = [d for _, _, d, *_ in evs]
    stack: list[int] = []
    for i, (_, s, d, *_) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = evs[stack[-1]]
            own[stack[-1]] -= min(s + d, p[1] + p[2]) - s
        stack.append(i)
    return own


def reduce_window(events: dict, lo: int, hi: int,
                  module: str | None = None) -> Window:
    """Sum ``events`` (from :func:`load_xplane`) over ``[lo, hi]`` ns.
    Busy time counts every device op; per-op sums take each op's own
    time (nested ops apart) and keep only ``module``'s ops where a
    module is named."""
    busy, op_s, op_calls, all_iv = [], {}, {}, []
    for evs in events["device"].values():
        evs = sorted(evs, key=lambda e: e[1])
        iv = [(s, s + d) for _, s, d, _ in evs]
        all_iv.append(iv)
        busy.append(union_ns(iv, lo, hi))
        for (name, s, d, mod), own in zip(evs, self_ns(evs)):
            if module and mod and mod != module:
                continue
            inside = min(s + d, hi) - max(s, lo)
            if inside <= 0:
                continue
            op_s[name] = op_s.get(name, 0.0) + own * inside / d * 1e-9
            op_calls[name] = op_calls.get(name, 0) + 1
    chips = max(len(busy), 1)
    gaps = []
    for iv in all_iv[:1]:
        for gs, ge in idle_gaps(iv, lo, hi):
            mid = (gs + ge) // 2
            act = [(d, n) for n, s, d in events["host"]
                   if n.startswith("bench.") and s <= mid < s + d]
            what = min(act)[1] if act else "host:other"
            gaps.append(((ge - gs) * 1e-9, what))
    gaps.sort(key=lambda g: -g[0])
    return Window(window_s=(hi - lo) * 1e-9,
                  busy_s=sum(busy) * 1e-9 / chips, op_s=op_s,
                  op_calls=op_calls, gaps=gaps)
