"""Layer-1 program auditor: walk closed jaxprs of the hot entry points.

Rules
-----
JAX001  float64 anywhere in the traced program.  x64 is never enabled in
        production; an f64 aval means a weak Python float (or an explicit
        np.float64 table) leaked past the `float()`-wrap convention and
        doubled the HBM traffic of everything downstream.
JAX002  dtype churn inside the declared bf16 interval: a state-sized
        f32 -> bf16 `convert_element_type` inside a scan/while body whose
        producer is an elementwise op.  That shape of convert only appears
        when f32 data (an un-cast operator matrix, a stray f32 constant)
        promoted the bf16 carry mid-loop and the result had to be demoted
        again — a full round trip per RK stage.  Demotes fed by reductions
        or `dot_general` are exempt: XLA accumulates bf16 sums/dots in f32
        on purpose (precision-improving, not churn).
JAX003  host callbacks (`pure_callback`/`io_callback`/`debug_callback`)
        inside a hot jitted program — a device->host sync per step.
JAX004  an entry point that declares donation expectations lowers with
        fewer aliased buffers than declared (donation silently dropped by
        a refactor; XLA only warns in logs nobody reads).
JAX005  un-donated output bytes above the entry's declared budget on a
        donating entry point.

Programs are traced with `jax.make_jaxpr` / `.lower()` only — nothing
executes, so the whole registry audits in seconds on CPU.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax._src import source_info_util

from .entrypoints import ENTRYPOINTS, Built, EntryPoint
from .report import Finding, Report

# Demote producers that are precision-improving, not churn: XLA upcasts
# f16/bf16 reduction + dot accumulators to f32 internally and hands back
# f32; converting that result down to the carry dtype is the intended
# mixed-precision pattern.
_ACCUMULATING_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "dot_general", "conv_general_dilated", "cumsum", "cumlogsumexp",
})

_LOOP_PRIMS = frozenset({"scan", "while"})

_CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback", "debug_callback"})


def _src(eqn) -> tuple[str, int]:
    try:
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return "", 0


def _sub_jaxprs(eqn):
    """All jaxprs nested inside one equation's params."""
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            if isinstance(item, jcore.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jcore.Jaxpr):
                yield item


def _walk(jaxpr, in_loop: bool = False):
    """Yield (eqn, in_loop, producer_prim_of_first_operand)."""
    producer: dict[int, str] = {}
    for eqn in jaxpr.eqns:
        op = eqn.invars[0] if eqn.invars else None
        op_prim = (producer.get(id(op), "") if isinstance(op, jcore.Var)
                   else "literal")
        yield eqn, in_loop, op_prim
        for v in eqn.outvars:
            producer[id(v)] = eqn.primitive.name
        inner_loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, inner_loop)


def _is_f64(aval) -> bool:
    return getattr(aval, "dtype", None) == jnp.dtype("float64")


def audit_entry(entry: EntryPoint, built: Built | None = None) -> list[Finding]:
    """All JAX* findings for one entry point (program-layer suppressions
    from `entry.suppress` applied)."""
    built = built or entry.build()
    closed = jax.make_jaxpr(built.fn)(*built.args, **built.kwargs)
    findings: list[Finding] = []

    def add(rule: str, message: str, file: str = "", line: int = 0) -> None:
        reason = entry.suppress.get(rule, "")
        findings.append(Finding(
            rule=rule, message=message, file=file, line=line,
            entrypoint=entry.name, suppressed=bool(reason),
            suppress_reason=reason))

    # --- JAX001 / JAX002 / JAX003: one recursive walk ------------------------
    f64_hits = 0
    for eqn, in_loop, op_prim in _walk(closed.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            file, line = _src(eqn)
            add("JAX003", f"{eqn.primitive.name} inside the jitted program",
                file, line)
        if any(_is_f64(v.aval) for v in eqn.outvars) and f64_hits < 5:
            f64_hits += 1
            file, line = _src(eqn)
            add("JAX001",
                f"float64 result of `{eqn.primitive.name}`", file, line)
        if (built.bf16_interval and in_loop
                and eqn.primitive.name == "convert_element_type"
                and eqn.params.get("new_dtype") == jnp.bfloat16
                and eqn.invars
                and getattr(eqn.invars[0].aval, "dtype", None)
                == jnp.dtype("float32")
                and eqn.invars[0].aval.size >= max(1, built.state_size // 4)
                and op_prim not in _ACCUMULATING_PRIMS):
            file, line = _src(eqn)
            add("JAX002",
                f"state-sized f32->bf16 demote (producer `{op_prim or 'loop carry'}`, "
                f"{eqn.invars[0].aval.size} elems) inside the bf16 interval "
                "— f32 data is promoting the carry mid-loop", file, line)

    # --- JAX004 / JAX005: donation via the lowered StableHLO -----------------
    if built.jit_fn is not None:
        jit_args = built.jit_args if built.jit_args is not None else built.args
        text = built.jit_fn.lower(*jit_args).as_text()
        aliased = {int(m) for m in
                   re.findall(r"tf\.aliasing_output\s*=\s*(\d+)", text)}
        if len(aliased) < built.expect_aliased:
            add("JAX004",
                f"expected >= {built.expect_aliased} donated (aliased) "
                f"buffers in the lowered program, found {len(aliased)}")
        if built.max_undonated_mb is not None:
            out_leaves = jax.tree.leaves(
                jax.eval_shape(built.fn, *built.args, **built.kwargs))
            undonated = sum(
                leaf.size * leaf.dtype.itemsize
                for i, leaf in enumerate(out_leaves) if i not in aliased)
            mb = undonated / 2**20
            if mb > built.max_undonated_mb:
                add("JAX005",
                    f"{mb:.2f} MB of un-donated outputs (budget "
                    f"{built.max_undonated_mb} MB) — donation dropped?")

    return findings


def run(report: Report | None = None,
        names: tuple[str, ...] | None = None) -> Report:
    """Audit every registered entry point (or the named subset)."""
    report = report or Report()
    audited = []
    for entry in ENTRYPOINTS:
        if names and entry.name not in names:
            continue
        report.extend(audit_entry(entry))
        audited.append(entry.name)
    report.meta.setdefault("jaxpr_audit", {})["entrypoints"] = audited
    return report
