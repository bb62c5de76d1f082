"""Device milliseconds per epoch of the collectives: the device ops whose
HLO opcode is ``all-to-all``, ``all-reduce``, ``all-gather``,
``reduce-scatter`` or ``collective-permute`` (or their ``-start`` /
``-done`` halves), and the fusions or async wrappers that call one, summed
over the traced window, divided by the epochs completed in it and
averaged over the chips.  On the mesh these are the halo exchange before
each aggregation (and its transpose) and the gradient ``psum``."""
import scopes

_KINDS = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
          "collective-permute")


def _is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start").removesuffix("-done") in _KINDS


def collective_ops(hlo_text: str) -> set[str]:
    """The instructions of ``hlo_text`` that are, or call, a collective."""
    instrs = scopes.parse(hlo_text)
    members: dict[str, list] = {}
    for ins in instrs.values():
        members.setdefault(ins.comp, []).append(ins)
    memo: dict[str, bool] = {}

    def holds(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(_is_collective(i.opcode)
                             or ("calls" in i.callees
                                 and holds(i.callees["calls"]))
                             for i in members.get(comp, ()))
        return memo[comp]

    return {n for n, i in instrs.items()
            if _is_collective(i.opcode)
            or ("calls" in i.callees and holds(i.callees["calls"]))}


def read(ctx):
    text = scopes.hlo_text_of(ctx)
    if not text or ctx.epochs == 0:
        return None
    names = collective_ops(text)
    s = sum(sec for n, sec in ctx.window.op_s.items() if n in names)
    return 1e3 * s / ctx.epochs / scopes.chips(ctx) if s > 0 else None
