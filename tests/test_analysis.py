"""Red-team tests for the static-analysis gate (`repro.analysis`).

Every rule id in `report.RULES` is exercised against deliberately
violating code — the analyzers are tested against known-bad programs,
not just the (clean) repo — plus clean-tree certification tests that
pin the repo itself at zero unsuppressed findings.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ast_rules, cli, jaxpr_audit, kernel_audit, trace_audit
from repro.analysis.entrypoints import Built, EntryPoint
from repro.analysis.report import RULES, Finding, Report


def _rules(findings):
    return {f.rule for f in findings}


def _lint(src, **kw):
    kw.setdefault("hot", True)
    kw.setdefault("kernel_module", False)
    kw.setdefault("registry_names", frozenset({"good_env"}))
    return ast_rules.lint_source("fixture.py", src, **kw)


# --- layer 2: AST rules ------------------------------------------------------
def test_ast001_numpy_in_traced_function():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def step(u: jax.Array):\n"
        "    return np.tanh(u)\n"
    )
    assert _rules(_lint(src)) == {"AST001"}


def test_ast001_exempt_host_table_builders_and_properties():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def table(cfg) -> np.ndarray:\n"          # no tracer param
        "    return np.arange(cfg.n)\n"
        "class C:\n"
        "    @property\n"
        "    def n_dof(self, u: jax.Array):\n"     # property math
        "        return np.prod(self.shape)\n"
    )
    assert _lint(src) == []


def test_ast001_silent_in_cold_modules():
    src = "import numpy as np\nimport jax\ndef f(u: jax.Array):\n    return np.abs(u)\n"
    assert _lint(src, hot=False) == []


def test_ast002_python_random():
    src = (
        "import random\n"
        "import jax\n"
        "def draw(u: jax.Array):\n"
        "    return random.random() + u\n"
    )
    assert _rules(_lint(src)) == {"AST002"}


def test_ast003_unwrapped_np_table_scalar():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "_RK_A = np.array([0.0, 1.0])\n"
        "def substep(du: jax.Array, stage: int):\n"
        "    return _RK_A[stage] * du\n"
    )
    assert _rules(_lint(src)) == {"AST003"}


def test_ast003_float_wrap_is_clean():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "_RK_A = np.array([0.0, 1.0])\n"
        "def substep(du: jax.Array, stage: int):\n"
        "    return float(_RK_A[stage]) * du\n"
    )
    assert _lint(src) == []


def test_ast004_jnp_float64():
    src = "import jax.numpy as jnp\nx = jnp.zeros((3,), jnp.float64)\n"
    assert _rules(_lint(src)) == {"AST004"}


def test_ast005_concrete_interpret_default():
    src = "def my_kernel(u, *, interpret: bool = True):\n    return u\n"
    assert _rules(_lint(src, kernel_module=True)) == {"AST005"}
    ok = "def my_kernel(u, *, interpret=None):\n    return u\n"
    assert _lint(ok, kernel_module=True) == []


def test_ast006_unregistered_env_name():
    src = "from repro import envs\nenv = envs.make('not_a_scenario')\n"
    assert _rules(_lint(src)) == {"AST006"}
    assert _lint("from repro import envs\nenv = envs.make('good_env')\n") == []


def test_ast007_suppression_requires_reason():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def step(u: jax.Array):\n"
        "    return np.tanh(u)  # repro-lint: disable=AST001\n"
    )
    rules = _rules(_lint(src))
    assert "AST007" in rules          # reasonless suppression is a finding
    assert "AST001" in rules          # ...and does NOT suppress


def test_suppression_with_reason_suppresses():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def step(u: jax.Array):\n"
        "    return np.tanh(u)  # repro-lint: disable=AST001 -- trace-time table\n"
    )
    findings = _lint(src)
    assert [f.rule for f in findings] == ["AST001"]
    assert findings[0].suppressed and findings[0].suppress_reason


# --- layer 1: jaxpr audit ----------------------------------------------------
def _audit(fn, args, **built_kw):
    built = Built(fn=fn, args=args, **built_kw)
    return jaxpr_audit.audit_entry(EntryPoint("fixture", lambda: built), built)


def test_jax001_f64_promotion():
    with jax.enable_x64(True):
        findings = _audit(lambda u: u.astype(jnp.float64) * 2.0,  # repro-lint: disable=AST004 -- deliberate f64 red-team fixture
                          (jnp.zeros((4,), jnp.float32),))
    assert "JAX001" in _rules(findings)


def test_jax002_bf16_interval_churn():
    def churned(u):
        d = jnp.ones((8, 8), jnp.float32)     # un-cast f32 operator

        def body(u, _):
            v = jnp.einsum("ij,jk->ik", d, u.astype(jnp.float32))
            rhs = v + 0.5 * v                 # elementwise f32 chain
            return u + rhs.astype(jnp.bfloat16) * 0.1, None

        u, _ = jax.lax.scan(body, u, None, length=3)
        return u

    u = jnp.zeros((8, 64), jnp.bfloat16)
    findings = _audit(churned, (u,), bf16_interval=True, state_size=u.size)
    assert "JAX002" in _rules(findings)


def test_jax002_reduction_upcast_is_clean():
    def accum(u):
        def body(u, _):
            # f32 accumulator of a bf16 sum: the intended mixed-precision
            # pattern — demoting it back must NOT count as churn
            e = jnp.sum(u.astype(jnp.float32) ** 2)
            return u * (1.0 - 1e-6 * e.astype(jnp.bfloat16)), None

        u, _ = jax.lax.scan(body, u, None, length=3)
        return u

    u = jnp.zeros((8, 64), jnp.bfloat16)
    findings = _audit(accum, (u,), bf16_interval=True, state_size=u.size)
    assert "JAX002" not in _rules(findings)


def test_jax003_host_callback():
    def with_callback(u):
        return jax.pure_callback(
            lambda x: x, jax.ShapeDtypeStruct(u.shape, u.dtype), u)

    findings = _audit(with_callback, (jnp.zeros((4,), jnp.float32),))
    assert "JAX003" in _rules(findings)


def test_jax004_dropped_donation():
    fn = lambda u: u + 1.0
    u = jnp.zeros((8,), jnp.float32)
    undonated = jax.jit(fn)                       # forgot donate_argnums
    findings = _audit(fn, (u,), jit_fn=undonated, expect_aliased=1)
    assert "JAX004" in _rules(findings)
    donated = jax.jit(fn, donate_argnums=(0,))
    assert _audit(fn, (u,), jit_fn=donated, expect_aliased=1) == []


def test_jax005_large_undonated_outputs():
    fn = lambda u: u * 2.0
    u = jnp.zeros((1 << 18,), jnp.float32)        # 1 MiB output, not donated
    findings = _audit(fn, (u,), jit_fn=jax.jit(fn), max_undonated_mb=0.5)
    assert "JAX005" in _rules(findings)


# --- layer 1: trace audit ----------------------------------------------------
def test_trace001_retrace_on_every_call():
    @jax.jit
    def f(u):
        return u * 2

    with trace_audit.watch({"f": f}) as w:
        f(jnp.zeros((3,)))
        f(jnp.zeros((4,)))                        # new shape -> retrace
    findings = w.check({"f": 1})
    assert [x.rule for x in findings] == ["TRACE001"]
    assert "retrace" in findings[0].message

    with trace_audit.watch({"f": f}) as w:
        f(jnp.zeros((3,)))                        # cached: zero growth
    assert w.check({"f": 0}) == []


def test_trace_certify_raises_on_mismatch():
    @jax.jit
    def g(u):
        return u + 1

    with pytest.raises(RuntimeError, match="trace certification failed"):
        trace_audit.certify({"g": g}, {"g": 1},
                            lambda: (g(jnp.zeros((2,))), g(jnp.zeros((3,)))))


def test_trace_watch_rejects_unjitted():
    with pytest.raises(TypeError, match="not a jitted callable"):
        trace_audit.watch({"f": lambda u: u})


# --- layer 1: kernel audit ---------------------------------------------------
def test_kern001_captured_array_constant():
    from jax.experimental import pallas as pl

    table = jnp.arange(8.0)                       # closure-captured array

    def bad_kernel(u_ref, o_ref):
        o_ref[...] = u_ref[...] * table

    def bad(u):
        return pl.pallas_call(
            bad_kernel,
            out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
            interpret=True)(u)

    findings, _ = kernel_audit.audit_kernel(
        "bad", bad, (jnp.zeros((8,), jnp.float32),), {})
    assert "KERN001" in _rules(findings)


def test_kern002_block_does_not_divide():
    from jax.experimental import pallas as pl

    def kern(u_ref, o_ref):
        o_ref[...] = u_ref[...] * 2

    def bad(u):
        return pl.pallas_call(
            kern,
            grid=(3,),
            in_specs=[pl.BlockSpec((4,), lambda i: (i,))],
            out_specs=pl.BlockSpec((4,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
            interpret=True)(u)

    findings, _ = kernel_audit.audit_kernel(
        "bad", bad, (jnp.zeros((10,), jnp.float32),), {})  # 4 !| 10
    assert "KERN002" in _rules(findings)


def test_kern003_vmem_budget():
    from repro.analysis.kernel_audit import _kernel_cases

    fn, args, kwargs = _kernel_cases()["dg_derivative3"]()
    findings, meta = kernel_audit.audit_kernel(
        "dg_derivative3", fn, args, kwargs, vmem_budget_mb=1e-6)
    assert "KERN003" in _rules(findings)
    assert meta["vmem_mb"] > 0


# --- the repo itself must be clean -------------------------------------------
def test_repo_ast_lint_clean():
    report = ast_rules.run(root=".")
    assert report.clean, report.summary()


def test_repo_kernel_audit_clean():
    report = kernel_audit.run()
    assert report.clean, report.summary()


def test_repo_jaxpr_audit_clean_and_bf16_interval_certified():
    report = jaxpr_audit.run()
    assert report.clean, report.summary()
    # the acceptance criterion: both bf16 advance entry points were walked
    audited = report.meta["jaxpr_audit"]["entrypoints"]
    assert "hit_advance_bf16" in audited and "channel_advance_bf16" in audited


def test_repo_trace_certification():
    report = trace_audit.run()
    assert report.clean, report.summary()
    counts = report.meta["trace_audit"]["reduced_hit_compile_counts"]
    assert counts == trace_audit.EXPECTED_REDUCED_HIT


# --- report / CLI plumbing ---------------------------------------------------
def test_report_schema_roundtrip(tmp_path):
    rep = Report(findings=[
        Finding(rule="AST001", message="m", file="f.py", line=3),
        Finding(rule="JAX002", message="s", entrypoint="e",
                suppressed=True, suppress_reason="why"),
    ])
    path = rep.save(str(tmp_path / "r.json"))
    data = json.loads(open(path).read())
    assert data["clean"] is False and data["n_findings"] == 1
    assert data["n_suppressed"] == 1
    assert data["findings_by_rule"] == {"AST001": 1}
    assert all(f["rule"] in RULES for f in data["findings"])


def test_cli_gates_on_findings(tmp_path):
    bad = tmp_path / "src" / "repro" / "envs"
    bad.mkdir(parents=True)
    (bad / "bad.py").write_text(
        "import numpy as np\nimport jax\n"
        "def step(u: jax.Array):\n    return np.tanh(u)\n")
    for sub in ("examples", "benchmarks", "tests"):
        (tmp_path / sub).mkdir()
    report_path = tmp_path / "analysis_report.json"
    rc = cli.main(["--layers", "ast", "--root", str(tmp_path),
                   "--report", str(report_path)])
    assert rc == 1
    assert json.loads(report_path.read_text())["findings_by_rule"] == {
        "AST001": 1}


def test_cli_rejects_unknown_layer():
    with pytest.raises(SystemExit):
        cli.main(["--layers", "nope"])


def test_every_rule_has_a_red_team_test():
    """Meta-test: the assertions above must cover the whole catalog."""
    covered = {
        "AST001", "AST002", "AST003", "AST004", "AST005", "AST006",
        "AST007", "JAX001", "JAX002", "JAX003", "JAX004", "JAX005",
        "TRACE001", "KERN001", "KERN002", "KERN003",
    }
    assert covered == set(RULES)


# --- satellite: REPRO_KERNELS validation -------------------------------------
def test_repro_kernels_env_validation(monkeypatch):
    from repro.kernels import policy

    for ok in ("kernel", "ref", "auto", "", "  KERNEL "):
        monkeypatch.setenv("REPRO_KERNELS", ok)
        policy.default_impl()                     # must not raise
    monkeypatch.setenv("REPRO_KERNELS", "kernels")
    with pytest.raises(ValueError) as e:
        policy.default_impl()
    msg = str(e.value)
    assert "REPRO_KERNELS" in msg and "'kernels'" in msg
    for accepted in ("kernel", "ref", "auto"):
        assert f"'{accepted}'" in msg
