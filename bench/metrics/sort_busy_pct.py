"""Kernels: device time in HLO `sort` operations over device busy time in
the traced window, grouped by opcode and not by fusion name."""


def read(view):
    t = view.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.by_opcode.get("sort", 0.0) / t.busy_s
