"""One compiled mesh program for the whole fleet (the paper's headline).

`fleet/pipeline.py` originally reproduced "hundreds of parallel
environments" as one dispatched rollout program PER SCENARIO — the XLA
queue hid cross-scenario stragglers, but nothing in the *program* did: a
slow sub-fleet serialized the device behind it.  This module merges the
whole heterogeneous fleet into ONE jitted program per iteration:

    step(params_k, opt_k, broker, k, keys_{k+1}) ->
        (params_{k+1}, opt_{k+1}, broker')

      inside the single program:
        1. update k    — consume traj_k from the broker rings, run the
                         joint multitask PPO update (non-finite guarded);
        2. rollout k+1 — every scenario's sub-fleet, laid out as a
                         scenario-major SUPER-BATCH: each scenario's env
                         batch padded up to the next multiple of the
                         `data`-axis size, the whole region
                         `shard_map`-ped over `data` so every device
                         advances a slice of EVERY scenario —
                         cross-scenario stragglers are load-balanced by
                         construction, not hidden by the dispatch queue;
        3. park        — padded trajectories are sliced back to their
                         real env counts (padding is masked out of the
                         loss by never reaching it: slicing happens
                         BEFORE GAE/advantage normalization, so pad rows
                         cannot skew the statistics; the scheduler's
                         per-scenario `weights` keep weighting the joint
                         loss exactly as before) and pushed into the
                         broker rings along with the update stats.

    Update k and rollout k+1 both read params_k — the double-buffered
    overlap `FleetRunner` used to get from two dispatches now lives inside
    one program, where XLA schedules the two dependency-free subgraphs
    itself.

Determinism: the rollout consumes the SAME per-(scenario, iteration) keys
(`scheduler.rollout_key = fold_in(fold_in(seed_key, i), k)`) and draws the
SAME random numbers as the per-scenario dispatch path — bank indices are
drawn at the REAL env count and padded afterwards, and the per-step action
noise is pre-drawn at the real count from the identical per-step key
stream, then padded.  The scan body is structurally identical to
`core/rollout.py` (which pre-draws noise as scan data for exactly this
reason), so on a single-`data`-shard mesh — where the padding is zero and
shapes match the dispatch path exactly — the super-batch rollout is
bit-identical to per-scenario dispatch (pinned by tests/test_fleet.py's
conformance test).  With real padding (a scenario's env count not
divisible by the `data` axis) the real rows stay bit-identical for
row-independent computations, but solvers whose compiled program tiles
over the batch (e.g. the fused Pallas HIT RHS) may differ at the ulp
level across batch widths — which is why padding is per-scenario minimal
rather than fleet-wide max.  The checkpoint state tree (params / opt /
broker) is unchanged in both structure and shapes either way.

Multi-host: the same program runs unmodified over a process-spanning mesh
(`launch/mesh.py: init_distributed + make_fleet_mesh`) on backends whose
runtime supports cross-process computations (TPU/GPU).  The CPU PJRT
backend does not; there, each process runs its local shard of the
collective-free rollout region (`rollout_shard`) — which is what the
multi-host CPU smoke test and the per-host scaling rows in
benchmarks/fleet_scaling.py exercise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import obs as obs_lib
from ..core import policy as policy_lib
from ..core import ppo as ppo_lib
from ..envs.base import EnvState
from . import broker as broker_lib
from . import multitask


def guarded_fleet_update(params, opt_state, ppo_cfg, mcfg, trajs, weights, k):
    """Joint multitask PPO update + the in-graph non-finite guard.

    The single shared implementation behind both the per-scenario dispatch
    path (`FleetRunner._update_impl`) and the single fleet program — the
    pipelined loop never syncs to inspect stats, so the revert decision
    must ride inside the program.
    """
    new_params, new_opt, stats = multitask.fleet_update(
        params, opt_state, ppo_cfg, mcfg, trajs, weights)
    ok = jnp.all(jnp.stack([jnp.all(jnp.isfinite(v))
                            for v in jax.tree.leaves(stats)]))
    keep = lambda new, old: jax.tree.map(
        lambda a, b: jnp.where(ok, a, b), new, old)
    stats = dict(stats)
    stats["update_ok"] = ok.astype(jnp.float32)
    stats["iteration"] = k.astype(jnp.float32)
    return keep(new_params, params), keep(new_opt, opt_state), stats


def slice_traj(traj: ppo_lib.Trajectory, n_envs: int) -> ppo_lib.Trajectory:
    """Drop the padding rows: (T, B_pad, ...) -> (T, n_envs, ...)."""
    return ppo_lib.Trajectory(
        obs=traj.obs[:, :n_envs],
        actions=traj.actions[:, :n_envs],
        log_probs=traj.log_probs[:, :n_envs],
        rewards=traj.rewards[:, :n_envs],
        dones=traj.dones[:, :n_envs],
        values=traj.values[:, :n_envs],
        last_value=traj.last_value[:n_envs],
        reverted=traj.reverted[:, :n_envs],
    )


_TRAJ_DATA_SPEC = ppo_lib.Trajectory(
    obs=P(None, "data"), actions=P(None, "data"), log_probs=P(None, "data"),
    rewards=P(None, "data"), dones=P(None, "data"), values=P(None, "data"),
    last_value=P("data"), reverted=P(None, "data"))


class FleetProgram:
    """The whole fleet's rollout+update iteration as one compiled program.

    Owns nothing the `FleetOrchestrator` doesn't already have — banks,
    envs, and the multitask policy come from the per-scenario
    orchestrators; this class only lays their work out as one program.
    """

    def __init__(self, forch, weights: dict[str, float],
                 ppo_cfg: ppo_lib.PPOConfig, *, mesh=None,
                 data_axis: str = "data"):
        self.forch = forch
        self.mcfg = forch.mcfg
        self.weights = weights
        self.ppo_cfg = ppo_cfg
        self.mesh = mesh
        self.data_axis = data_axis
        self.n_envs = {m.name: m.n_envs for m in forch.schedule.members}
        self.n_data = (int(mesh.shape[data_axis])
                       if mesh is not None and data_axis in mesh.shape else 1)
        # per-scenario super-batch width: padded up to the next multiple of
        # the `data` axis so shard_map splits it evenly.  Minimal padding
        # (not fleet-wide max) keeps batch shapes equal to the dispatch
        # path whenever `data` divides the env count — the precondition
        # for bit-identical conformance (see module docstring).
        self.b_pad = {n: -(-b // self.n_data) * self.n_data
                      for n, b in self.n_envs.items()}
        # one compiled program per iteration; opt state and broker rings
        # donate (their buffers update in place), params do not alias their
        # output (the guard may keep the old tree) but params_k has no
        # external reader after the call, so donation would also be sound —
        # kept undonated to match the dispatch path's audit expectations.
        self._step = jax.jit(self._step_impl, donate_argnums=(1, 2))
        self._prologue = jax.jit(self._prologue_impl, donate_argnums=(1,))
        self._registered = False

    @property
    def names(self) -> tuple[str, ...]:
        return self.forch.names

    # --- deterministic input draws -------------------------------------------
    def draw_padded_inputs(self, name: str, key: jax.Array
                           ) -> tuple[jax.Array, jax.Array]:
        """(u0, noise) for scenario `name`, padded to the super-batch width.

        Bit-compatible with the dispatch path: bank indices and per-step
        action noise are drawn at the REAL env count from the same key
        splits `Orchestrator.sample_fleet` + `rollout` use, THEN padded
        (pad rows replay bank row 0 with zero noise; they are sliced off
        before the broker/loss ever see them).
        """
        orch = self.forch.orchs[name]
        n = self.n_envs[name]
        pad = self.b_pad[name] - n
        k_init, k_roll = jax.random.split(key)
        idx = jax.random.randint(k_init, (n,), 0, orch.fleet.bank_size - 1)
        if pad:
            idx = jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)])
        u0 = jnp.take(orch.bank, idx, axis=0)
        act_shape = orch.env.action_spec.shape
        step_keys = jax.random.split(k_roll, orch.env.n_actions)
        noise = jax.vmap(
            lambda kk: jax.random.normal(kk, (n,) + act_shape))(step_keys)
        if pad:
            noise = jnp.concatenate(
                [noise, jnp.zeros(noise.shape[:1] + (pad,) + act_shape,
                                  noise.dtype)], axis=1)
        return u0, noise

    # --- the shard_map-ped rollout region ------------------------------------
    def _scan_rollout(self, name: str, params: dict, u0: jax.Array,
                      noise: jax.Array) -> ppo_lib.Trajectory:
        """core/rollout.py's scan with the action noise passed in as data
        (so the noise stream is independent of the padded batch width and
        of how `data` shards it)."""
        env = self.forch.orchs[name].env
        pol = multitask.policy_fns(self.mcfg, name)
        state0 = EnvState(u=u0, t_step=jnp.zeros((u0.shape[0],), jnp.int32))

        def step_fn(state: EnvState, noise_t: jax.Array):
            obs = env.observe(state)
            with jax.named_scope("rollout.policy"):
                mean, std = pol.dist(params, obs)
                action = mean + std * noise_t
                logp = policy_lib.log_prob(mean, std, action)
                val = pol.value(params, obs)
            res = env.step(state, action)
            return res.state, (obs, action, logp, res.reward, res.done, val,
                               res.reverted)

        final_state, (obs, actions, log_probs, rewards, dones, values,
                      reverted) = jax.lax.scan(step_fn, state0, noise)
        obs_last = env.observe(final_state)
        with jax.named_scope("rollout.policy"):
            last_value = pol.value(params, obs_last)
        return ppo_lib.Trajectory(obs=obs, actions=actions,
                                  log_probs=log_probs, rewards=rewards,
                                  dones=dones, values=values,
                                  last_value=last_value, reverted=reverted)

    def rollout_shard(self, params: dict, u0s: dict, noises: dict
                      ) -> dict[str, ppo_lib.Trajectory]:
        """Advance every scenario's (already laid-out) env batch — the body
        of the shard_map region.  Collective-free: each device touches only
        its own rows of every scenario, which is exactly what makes the
        super-batch layout straggler-proof (and lets a CPU multi-host
        smoke run one process's shard standalone)."""
        return {name: self._scan_rollout(name, params, u0s[name],
                                         noises[name])
                for name in self.names}

    @jax.named_scope("fleet.rollout")
    def rollout_super_batch(self, params: dict, keys: dict[str, jax.Array]
                            ) -> dict[str, ppo_lib.Trajectory]:
        """One rollout pass over the whole fleet; returns PADDED
        trajectories (B_pad envs per scenario)."""
        drawn = {n: self.draw_padded_inputs(n, keys[n]) for n in self.names}
        u0s = {n: uv[0] for n, uv in drawn.items()}
        noises = {n: uv[1] for n, uv in drawn.items()}
        if self.mesh is None:
            return self.rollout_shard(params, u0s, noises)
        fn = jax.shard_map(
            self.rollout_shard, mesh=self.mesh,
            in_specs=(P(),  # params: replicated
                      {n: P(self.data_axis) for n in self.names},
                      {n: P(None, self.data_axis) for n in self.names}),
            out_specs={n: _TRAJ_DATA_SPEC for n in self.names},
            check_vma=False)
        return fn(params, u0s, noises)

    # --- the compiled iteration ----------------------------------------------
    # named scopes (repro.obs) mark each part's ops for the device trace
    def _park(self, broker, padded):
        with jax.named_scope("fleet.broker"):
            for n in self.names:
                broker = broker_lib.push_traj(
                    broker, n, slice_traj(padded[n], self.n_envs[n]))
        return broker

    def _step_impl(self, params, opt_state, broker, k, keys):
        with jax.named_scope("fleet.broker"):
            trajs_k = {n: broker_lib.latest_traj(broker, n)
                       for n in self.names}
        with jax.named_scope("fleet.update"):
            new_params, new_opt, stats = guarded_fleet_update(
                params, opt_state, self.ppo_cfg, self.mcfg, trajs_k,
                self.weights, k)
        broker = self._park(broker, self.rollout_super_batch(params, keys))
        with jax.named_scope("fleet.broker"):
            broker = broker_lib.push_metrics(broker, "fleet", stats)
        return new_params, new_opt, broker

    def _prologue_impl(self, params, broker, keys):
        """Iteration-0 priming: rollout + park, no update (the broker must
        hold traj_0 before the first in-program update can consume it)."""
        return self._park(broker, self.rollout_super_batch(params, keys))

    def step(self, params, opt_state, broker, k, keys):
        """Dispatch iteration k: update k + rollout k+1 + broker pushes,
        one XLA program.  `opt_state` and `broker` are DONATED.  The first
        dispatch registers the program for `repro.obs.op_scopes()`."""
        if not self._registered:
            obs_lib.register_program("fleet.step", self._step,
                                     (params, opt_state, broker, k, keys))
            self._registered = True
        return self._step(params, opt_state, broker, k, keys)

    def prologue(self, params, broker, keys):
        """Dispatch the priming rollout for iteration 0 (`broker` donated)."""
        return self._prologue(params, broker, keys)
