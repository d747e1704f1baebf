"""The registry of hot compiled entry points the program auditors walk.

These are the programs whose compiled form IS the product — the per-step
solver advance, the fleet rollout, the PPO/fleet updates, the fused RHS
mega-kernel (natural-layout wrapper and the planar entry the RK loop
calls), the channel's wall variant of it, and the broker's donated push.  `jaxpr_audit.audit_entry`
traces each one at a reduced (but structurally faithful) shape and checks
the resulting jaxpr/StableHLO against the compiled-program invariants; the
trace auditor re-drives a subset through a reduced training run and pins
compile counts.

Every entry is built lazily (`build()`), at shapes small enough that the
whole registry traces in seconds on CPU.  Audits here never *execute* the
programs — tracing and lowering only.

Program-layer suppressions live on the entry (`suppress={"RULE": reason}`)
so waivers are code-reviewed, not scattered comments.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Built:
    """One traced-auditable program: `fn(*args, **kwargs)` must trace."""

    fn: Callable
    args: tuple
    kwargs: dict = dataclasses.field(default_factory=dict)
    # bf16-interval audit (JAX002): the declared mixed-precision interval —
    # inside the advance loop the carried state must stay bf16 (state-sized
    # f32 round trips are churn; reduction-accumulator upcasts are not).
    bf16_interval: bool = False
    state_size: int = 0            # elements of the carried state array
    # donation audit (JAX004/JAX005): lowered aliasing expectations.  Only
    # meaningful when `jit_fn` is the production jit wrapper (donation is a
    # jit-boundary property, not a function property).
    jit_fn: Any = None
    jit_args: tuple | None = None  # call args for jit_fn (defaults to `args`)
    expect_aliased: int = 0        # minimum donated (aliased) input buffers
    max_undonated_mb: float | None = None


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str
    build: Callable[[], Built]
    suppress: dict = dataclasses.field(default_factory=dict)


def _hit_cfg(precision: str = "fp32"):
    from ..cfd.solver import HITConfig
    return HITConfig(n_poly=3, n_elem=2, t_end=0.5, precision=precision,
                     use_kernels=False)


def _build_hit_advance(precision: str) -> Built:
    import jax
    import jax.numpy as jnp

    from ..cfd import initial, solver

    cfg = _hit_cfg(precision)
    u = initial.sample_initial_state(jax.random.PRNGKey(0), cfg)
    cs = jnp.full((cfg.n_elem,) * 3, 0.17, jnp.float32)
    return Built(fn=lambda u, cs: solver.advance_rl_interval(u, cs, cfg),
                 args=(u, cs), bf16_interval=(precision == "bf16"),
                 state_size=u.size)


def _build_channel_advance(precision: str) -> Built:
    import jax
    import jax.numpy as jnp

    from ..cfd import channel as channel_mod
    from ..cfd.channel import ChannelConfig

    cfg = ChannelConfig(n_elem=(2, 3, 2), precision=precision,
                        use_kernels=False)
    u = channel_mod.sample_initial_state(jax.random.PRNGKey(1), cfg)
    kx, _, kz = cfg.n_elem
    scale = jnp.ones((kx, kz), jnp.float32)
    return Built(
        fn=lambda u, sb, st: channel_mod.advance_rl_interval(u, sb, st, cfg),
        args=(u, scale, scale), bf16_interval=(precision == "bf16"),
        state_size=u.size)


def _build_rollout() -> Built:
    import jax

    from .. import envs
    from ..core import policy as policy_lib
    from ..core import rollout as rollout_lib

    env = envs.make("hit_les_reduced")
    pcfg = policy_lib.PolicyConfig.from_specs(env.obs_spec, env.action_spec)
    params = policy_lib.init(jax.random.PRNGKey(0), pcfg)
    u0 = env.initial_state_bank(jax.random.PRNGKey(1), 2)
    key = jax.random.PRNGKey(2)
    return Built(
        fn=lambda params, u0, key: rollout_lib.rollout(
            params, pcfg, env, u0, key),
        args=(params, u0, key))


def _ppo_traj(env, pcfg, params, n_envs: int = 2):
    """A zero trajectory with the exact rollout output structure."""
    import jax
    import jax.numpy as jnp

    from ..core import rollout as rollout_lib

    u0 = env.initial_state_bank(jax.random.PRNGKey(1), n_envs)
    shapes = jax.eval_shape(
        lambda p, u, k: rollout_lib.rollout(p, pcfg, env, u, k),
        params, u0, jax.random.PRNGKey(2))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _build_ppo_update() -> Built:
    import jax

    from .. import envs, optim
    from ..core import policy as policy_lib
    from ..core import ppo as ppo_lib

    env = envs.make("hit_les_reduced")
    pcfg = policy_lib.PolicyConfig.from_specs(env.obs_spec, env.action_spec)
    params = policy_lib.init(jax.random.PRNGKey(0), pcfg)
    opt_state = optim.adam_init(params)
    cfg = ppo_lib.PPOConfig()
    traj = _ppo_traj(env, pcfg, params)
    return Built(
        fn=lambda p, o, t: ppo_lib.update(p, o, cfg, pcfg, t),
        args=(params, opt_state, traj))


def _build_fleet_update() -> Built:
    import tempfile

    import jax
    import jax.numpy as jnp

    from ..fleet.pipeline import FleetRunnerConfig, make_fleet_runner

    runner = make_fleet_runner(
        ("hit_les_reduced", "burgers_reduced"), total_envs=2,
        run_cfg=FleetRunnerConfig(
            checkpoint_dir=tempfile.mkdtemp(prefix="repro_audit_"),
            async_checkpoint=False))
    shapes = {name: jax.eval_shape(runner.forch.orchs[name].sample_fleet,
                                   runner.params, jax.random.PRNGKey(0))
              for name in runner.forch.names}
    trajs = {n: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), t)
             for n, t in shapes.items()}
    return Built(
        fn=lambda p, o, t: runner._update_impl(p, o, t,
                                               jnp.zeros((), jnp.int32)),
        args=(runner.params, runner.opt_state, trajs),
        jit_fn=runner._update,
        jit_args=(runner.params, runner.opt_state, trajs,
                  jnp.zeros((), jnp.int32)),
        # the optimizer state (m, v moments) is donated; params/stats are
        # deliberately NOT (the overlapped rollout still reads params_k)
        expect_aliased=1, max_undonated_mb=8.0)


def _build_fleet_program() -> Built:
    import tempfile

    import jax.numpy as jnp

    from ..fleet.pipeline import FleetRunnerConfig, make_fleet_runner

    runner = make_fleet_runner(
        ("hit_les_reduced", "burgers_reduced"), total_envs=2,
        run_cfg=FleetRunnerConfig(
            checkpoint_dir=tempfile.mkdtemp(prefix="repro_audit_"),
            async_checkpoint=False))
    prog = runner.program
    args = (runner.params, runner.opt_state, runner.broker,
            jnp.zeros((), jnp.int32), runner._keys(1))
    return Built(
        fn=prog._step_impl, args=args,
        jit_fn=prog._step,
        # the optimizer state and the broker rings update in place; params
        # are NOT donated (the guard may keep the old tree, and the audit
        # mirrors the dispatch path's expectations)
        expect_aliased=2, max_undonated_mb=None)


def _build_broker_push() -> Built:
    import jax.numpy as jnp

    from ..fleet import broker as broker_lib

    item = {
        "obs": jnp.zeros((3, 2, 8, 4, 4, 4, 3), jnp.float32),
        "rewards": jnp.zeros((3, 2), jnp.float32),
    }
    ring = broker_lib.ring_init(item, 2)
    return Built(fn=broker_lib.push, args=(ring, item),
                 jit_fn=broker_lib.push_donated,
                 # every ring buffer (and the head counter) updates in place
                 expect_aliased=1, max_undonated_mb=1.0)


def _build_fused_rhs() -> Built:
    import jax
    import jax.numpy as jnp

    from ..cfd import initial
    from ..kernels import rhs as rhs_mod

    cfg = _hit_cfg()
    ops_d = cfg.operators()
    u = initial.sample_initial_state(jax.random.PRNGKey(0), cfg)
    cs = jnp.full(u.shape[:-1], 0.17, u.dtype)
    return Built(
        fn=lambda u, cs: rhs_mod.fused_navier_stokes_rhs(
            u, cs, ops_d["D"], ops_d["w"], inv_w_end=ops_d["inv_w_end"],
            jac=cfg.dg.jac, delta=cfg.delta_filter, mu=cfg.gas.mu,
            prandtl=cfg.prandtl, prandtl_turb=cfg.prandtl_turb,
            forcing_a0=cfg.forcing_a0, k_tke=cfg.k_tke, interpret=True),
        args=(u, cs))


def _build_fused_rhs_planar() -> Built:
    import jax
    import jax.numpy as jnp

    from ..cfd import initial
    from ..kernels import rhs as rhs_mod

    cfg = _hit_cfg()
    ops_d = cfg.operators()
    u = initial.sample_initial_state(jax.random.PRNGKey(0), cfg)
    u_pl, cs_pl, block_e = rhs_mod.to_planar_batch(
        u, jnp.full(u.shape[:-1], 0.17, u.dtype))
    return Built(
        fn=lambda u, cs: rhs_mod.fused_navier_stokes_rhs_planar(
            u, cs, ops_d["D"], ops_d["w"], k=cfg.n_elem, block_e=block_e,
            inv_w_end=ops_d["inv_w_end"], jac=cfg.dg.jac,
            delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
            prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
            k_tke=cfg.k_tke, interpret=True),
        args=(u_pl, cs_pl))


def _build_wall_rhs_planar() -> Built:
    import jax
    import jax.numpy as jnp

    from ..cfd import channel as channel_mod
    from ..cfd.channel import ChannelConfig
    from ..kernels import rhs as rhs_mod

    cfg = ChannelConfig(n_elem=(2, 4, 2), use_kernels=False)
    ops_d = cfg.operators()
    u = channel_mod.make_state_bank(jax.random.PRNGKey(1), cfg, 2)
    kx, _, kz = cfg.n_elem
    scale = jnp.ones((2, kx, kz), u.dtype)
    u_pl, scale_pl, block_e = rhs_mod.to_planar_wall_batch(
        u, channel_mod.wall_scale_elements(scale, scale, cfg))
    return Built(
        fn=lambda u, s: rhs_mod.fused_wall_rhs_planar(
            u, s, ops_d["D"], ops_d["w"], k=cfg.n_elem, block_e=block_e,
            interpret=True, **channel_mod.wall_rhs_scalars(cfg, ops_d)),
        args=(u_pl, scale_pl))


def _build_serve_step() -> Built:
    import jax
    import jax.numpy as jnp

    from .. import envs
    from ..fleet import multitask
    from ..serve import service as serve_lib

    name = "hit_les_reduced"
    mcfg = multitask.MultiTaskConfig.from_envs(
        [(n, envs.make(n)) for n in (name, "burgers_reduced")])
    params = multitask.init(jax.random.PRNGKey(0), mcfg)
    head = mcfg.head(name)
    obs = jnp.zeros((2, head.n_elements, *head.spatial, head.channels),
                    jnp.float32)
    n_valid = jnp.asarray(2, jnp.int32)
    stats = jnp.zeros((2,), jnp.int32)
    svc = serve_lib.ControllerService(params, mcfg)
    return Built(
        fn=lambda p, o, n, s: serve_lib.serve_step(p, mcfg, name, o, n, s),
        args=(params, obs, n_valid, stats),
        jit_fn=svc._step,
        jit_args=(params, mcfg, name, obs, n_valid, stats),
        # the telemetry counter is donated (in-place add per dispatch);
        # actions/values are real outputs and stay small at serving shapes
        expect_aliased=1, max_undonated_mb=1.0)


ENTRYPOINTS: tuple[EntryPoint, ...] = (
    EntryPoint("hit_advance", lambda: _build_hit_advance("fp32")),
    EntryPoint("hit_advance_bf16", lambda: _build_hit_advance("bf16")),
    EntryPoint("channel_advance", lambda: _build_channel_advance("fp32")),
    EntryPoint("channel_advance_bf16",
               lambda: _build_channel_advance("bf16")),
    EntryPoint("rollout", _build_rollout),
    EntryPoint("ppo_update", _build_ppo_update),
    EntryPoint("fleet_update", _build_fleet_update),
    EntryPoint("fleet_program", _build_fleet_program),
    EntryPoint("broker_push", _build_broker_push),
    EntryPoint("fused_rhs", _build_fused_rhs),
    EntryPoint("fused_rhs_planar", _build_fused_rhs_planar),
    EntryPoint("wall_rhs_planar", _build_wall_rhs_planar),
    EntryPoint("serve_step", _build_serve_step),
)


def get(name: str) -> EntryPoint:
    for e in ENTRYPOINTS:
        if e.name == name:
            return e
    raise KeyError(f"unknown entry point {name!r}; have "
                   f"{tuple(e.name for e in ENTRYPOINTS)}")
