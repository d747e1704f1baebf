"""Observability: host spans and the named-scope map of compiled programs.

The one module of the package that talks to `jax.profiler`.

* `span(name)` — a host span: a `jax.profiler.TraceAnnotation` (so it
  lands on the profiler's host plane, on the device ops' clock, whenever a
  trace is being taken) plus a `(name, start, end)` record on
  `time.perf_counter` in a bounded in-memory ring that `spans()` returns.
* `register_program(name, jitted, args)` — remembers a jitted program and
  the abstract shapes of one call.  It lowers and compiles nothing.
* `op_scopes()` — for every registered program, the map from each compiled
  HLO instruction name (the names a device trace gives its operations) to
  the named scope (`jax.named_scope`) it came from.  Built lazily by
  lowering and compiling the program again from the stored shapes — a hit
  in the persistent compile cache where one is enabled — and parsing the
  optimized HLO's `metadata={op_name=...}`.

A scope is the innermost lower-case dotted `layer.part` component of an
`op_name` path such as `jit(_step_impl)/fleet.rollout/shard_map/while/
body/solver.rk_substep/add`; the `jit(...)`, `while/body`, `shard_map`
and primitive components are not scopes, nor are the `Class.method`
names JAX puts in some paths, and transforms are seen through
(`transpose(jvp(fleet.update))` is `fleet.update`).  An instruction that
XLA created without an `op_name` (a loop-carried copy) takes the scope of
the loop or call instruction whose computation holds it.

The scopes the training step carries (see `fleet/superbatch.py`,
`cfd/solver.py`, `kernels/rhs.py`) and the host span around its dispatch
(`fleet/pipeline.py`):

    fleet.update       PPO update: policy forward and backward, the guard
    fleet.broker       broker ring reads and donated ring writes
    fleet.rollout      bank draw, noise, scan plumbing, observe and reward
    rollout.policy     policy forward inside the rollout scan
    solver.rk_substep  RK stage arithmetic
    rhs.layout         conversions to and from the fused RHS kernel's
                       planar layout: once per RL interval around the RK
                       loop, around each call of the natural-layout
                       wrapper; and the kernel's constant columns
    fleet.dispatch     (host span) key derivation and the step's dispatch
"""
from __future__ import annotations

import collections
import contextlib
import re
import time

import jax

_SPANS: collections.deque = collections.deque(maxlen=4096)
_PROGRAMS: dict[str, tuple] = {}
_SCOPES: dict[str, tuple[str, dict]] = {}   # program name -> (module, map)

_SCOPE = re.compile(r"^[a-z_][a-z0-9_]*\.[a-z_][a-z0-9_]*$")
_COMP = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_FUSION = re.compile(r"(^|\s)fusion\(")
_OPERAND_FREE = re.compile(r"(^|\s)(constant|parameter)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations|"
    r"true_computation|false_computation|called_computations)="
    r"(\{[^}]*\}|%[\w.\-]+)")


@contextlib.contextmanager
def span(name: str):
    """Record the enclosed host work as span `name`."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            _SPANS.append((name, t0, time.perf_counter()))


def spans() -> list[tuple[str, float, float]]:
    """The newest host spans, oldest first: (name, start, end) in
    `time.perf_counter` seconds."""
    return list(_SPANS)


def _abstract(x):
    if not isinstance(x, jax.Array):
        return x
    # an uncommitted array lowers without a sharding annotation; giving it
    # one would lower (and compile) a different program
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None,
        weak_type=x.weak_type)


def register_program(name: str, jitted, args: tuple) -> None:
    """Remember `jitted` and the shapes of `args` (one of its calls) under
    `name`, replacing an earlier program of that name.  Call it before a
    call that donates `args`."""
    _PROGRAMS[name] = (jitted, jax.tree.map(_abstract, args))
    _SCOPES.pop(name, None)


def scope_of(op_name: str | None) -> str | None:
    """The innermost named scope of an `op_name` path, or None."""
    # the first component is the outer jit, or an argument's name
    for part in reversed((op_name or "").split("/")[1:]):
        while part.endswith(")") and "(" in part:    # transform(...)
            part = part[part.index("(") + 1:-1]
        if _SCOPE.match(part):
            return part
    return None


def _called(rest: str) -> list[str]:
    out = []
    for _, ref in _CALLED.findall(rest):
        out.extend(c.strip().lstrip("%") for c in ref.strip("{}").split(",")
                   if c.strip())
    return out


def parse_hlo(text: str) -> tuple[str, dict]:
    """(module name, {instruction: (scope, fused scopes)}) of an optimized
    HLO module's text.  Instructions of fusion computations do not run as
    operations of their own and are left out; each fusion carries the set
    of scopes of the instructions fused into it, and its own scope is its
    root's.  Instructions with no scope and no scoped fused instruction
    are left out."""
    lines = text.splitlines()
    module = lines[0].split()[1].rstrip(",") if lines else ""
    comps: dict[str, list] = {}   # computation -> [(name, meta, calls, ...)]
    operand_free = set()          # constants and parameters
    cur = None
    for line in lines:
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            rest = m.group(3)
            op_name = _OP_NAME.search(rest)
            if _OPERAND_FREE.search(rest):
                operand_free.add(m.group(2))
            cur.append((m.group(2), op_name.group(1) if op_name else None,
                        _called(rest), bool(_FUSION.search(rest)),
                        bool(m.group(1))))
    fused, caller, comp_of, meta = set(), {}, {}, {}
    for comp, instrs in comps.items():
        for name, op_name, called, is_fusion, _ in instrs:
            comp_of[name], meta[name] = comp, op_name
            for c in called:
                caller.setdefault(c, name)
                if is_fusion:
                    fused.add(c)

    def own(name: str) -> str | None:
        """The instruction's scope; XLA-made instructions (no op_name)
        take the scope of the loop or call that holds them."""
        seen = set()
        while meta[name] is None and name not in seen:
            seen.add(name)
            up = caller.get(comp_of[name])
            if up is None:
                return None
            name = up
        return scope_of(meta[name])

    def inner(called: list[str]) -> set[str]:
        out = set()
        for c in called:
            for name, op_name, sub, _, _ in comps.get(c, []):
                if scope_of(op_name) and name not in operand_free:
                    out.add(scope_of(op_name))
                out |= inner(sub)
        return out

    table = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, op_name, called, is_fusion, _ in instrs:
            scope, scopes = own(name), set()
            if is_fusion:
                scopes = inner(called)
                root = [i[1] for c in called for i in comps.get(c, [])
                        if i[4]]
                if root and scope_of(root[0]):
                    scope = scope_of(root[0])
            if scope or scopes:
                table[name] = (scope, frozenset(scopes))
    return module, table


def op_scopes() -> dict[str, dict[str, tuple[str | None, frozenset]]]:
    """{HLO module name: {instruction name: (scope of the instruction, set
    of scopes of the instructions fused into it)}} of every registered
    program.  Lowers and compiles each program from its stored shapes the
    first time; call it outside any timed region."""
    out = {}
    for name, (jitted, args) in _PROGRAMS.items():
        if name not in _SCOPES:
            text = jitted.lower(*args).compile().as_text()
            _SCOPES[name] = parse_hlo(text)
        module, table = _SCOPES[name]
        out[module] = table
    return out
