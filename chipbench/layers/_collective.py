"""What the collective-router readers share (the leading underscore keeps
this module out of ``layers.load()``: it is no reader).

The predicate reads the configuration's ``server_flags`` — the flag a
user would pass — and never a cell's name: any closed-loop cell whose
deployment is served with ``--router collective`` reports these."""

from chipbench.layers import closed_loop

LAYER = "collective routing"


def collective_closed(cell: dict) -> bool:
    flags = cell["config"].get("server_flags") or []
    routed = any(a == "--router" and b == "collective"
                 for a, b in zip(flags, flags[1:]))
    return routed and closed_loop(cell)
