"""Device time by named scope: each device operation of a traced window
takes the scope of the compiled instruction it ran.

The map from instruction names to scopes comes from the program itself
(`repro.obs.op_scopes()`: the optimized HLO's `op_name` metadata of every
program the system registered).  An operation is looked up in the map of
the module its trace label names, and by name alone when the label names
no registered module.  A fusion counts under its root's scope; the fused
RHS kernel's own operations count under `KERNEL`; an operation the map
does not know counts as unscoped (`None`).

Time is the device time of the innermost operations inside the window,
averaged over the device planes.  Where operations overlap, the overlap
counts once, for the operation that started first, so the scope times
add up to the union of the innermost operations.

A program without `repro.obs`, or one that registered nothing, gives no
map: the readers then return None.
"""
from __future__ import annotations

from bench import trace as trace_lib

KERNEL = "kernel"


def scope_map():
    """{module: {instruction: (scope, fused scopes)}}, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.op_scopes() or None


def lookup(scopes: dict, name: str, label: str):
    """(scope, fused scopes) of one operation, or None if unknown."""
    tables = [t for mod, t in scopes.items() if mod and mod in label]
    for table in tables or scopes.values():
        if name in table:
            return table[name]
    return None


def seconds_by_scope(tr, scopes: dict, kernel: str) -> dict:
    """{scope, KERNEL or None: device seconds in the window}."""
    lo, hi = tr.window
    out: dict = {}
    for evs in tr.ops.values():
        covered = lo
        for name, label, s, e in sorted(trace_lib.leaves(evs),
                                        key=lambda ev: ev[2]):
            own = min(e, hi) - max(s, covered)
            covered = max(covered, min(e, hi))
            if own <= 0:
                continue
            if name == kernel or name.startswith(kernel + "."):
                where = KERNEL
            else:
                hit = lookup(scopes, name, label)
                where = hit[0] if hit else None
            out[where] = out.get(where, 0.0) + own / len(tr.ops)
    return out


def share(ctx, scope) -> float | None:
    """Percent of the traced window that the device spent in operations
    of `scope` (None: unscoped), or None when nothing can be read."""
    tr = ctx.get("trace")
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    scopes = scope_map()
    if not scopes or scope not in {None} | {
            s for table in scopes.values() for s, _ in table.values()}:
        return None
    secs = seconds_by_scope(tr, scopes, ctx["rhs_kernel"])
    return 100.0 * secs.get(scope, 0.0) / tr.window_s

