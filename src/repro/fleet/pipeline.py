"""Pipelined heterogeneous-fleet training: the multi-scenario front-end.

`FleetOrchestrator` lays one mesh's environment budget out as per-scenario
sub-fleets (one core `Orchestrator` each, so banks, sharding, and the
jitted rollout programs are exactly the single-scenario machinery), and
`FleetRunner` drives them through a double-buffered rollout/update pipeline
brokered by `fleet/broker.py`.

With `single_program=True` (the default) the whole iteration is ONE
compiled program (`fleet/superbatch.py`): the per-scenario sub-fleets are
laid out as a scenario-major super-batch, `shard_map`-ped over the mesh's
`data` axis, and update k + rollout k+1 + the broker pushes all live in a
single XLA dispatch — cross-scenario stragglers are load-balanced inside
the program instead of hidden by the dispatch queue.  With
`single_program=False` the pre-PR-8 per-scenario dispatch path runs
instead (kept as the measured baseline for
`benchmarks/fleet_scaling.py: single_program_vs_dispatch_speedup`, and as
the reference side of the bit-identity conformance pin):

    iteration k (pipelined):
        traj_k        <- broker slot k % 2        (rolled last iteration)
        dispatch  update_k(params_k, traj_k)      -> params_{k+1}
        dispatch  rollout_{k+1}(params_k)         (all sub-fleets)
        dispatch  push traj_{k+1} -> slot (k+1)%2 (donated, in-place)
        dispatch  push stats_k -> metrics ring    (no device_get)

    Nothing in the loop blocks on the device: the host runs ahead
    enqueueing work, rollout k+1 and update k overlap (in ONE program by
    default, in the XLA queue on the dispatch path — they share only
    params_k, which both read), and metric traffic stays device-resident
    until a checkpoint boundary drains it.  The price is the standard
    one-iteration policy lag (traj_k was rolled with params_{k-1});
    `pipelined=False` recovers the paper's strictly synchronous semantics,
    and `benchmarks/fleet_scaling.py` measures the overlap win of the
    default.

Determinism contract (the multi-scenario extension of core/runner.py's):
iteration k of scenario i is a pure function of (seed, i, k, params) —
rollout keys are `fold_in(fold_in(seed_key, i), k)`, bank seeds are
`scheduler.scenario_seed(seed, i)`, and the checkpoint state tree carries
params + optimizer + THE BROKER (the in-flight trajectory included), so a
restored pipelined run replays bit-identically (pinned by
tests/test_fleet.py's mixed-fleet replay test).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from .. import obs, optim
from ..core import ppo as ppo_lib
from ..core.orchestrator import FleetConfig, Orchestrator
from ..core.runner import RunnerBase, RunnerConfig
from . import broker as broker_lib
from . import multitask, scheduler as sched_lib
from . import superbatch as superbatch_lib
from .scheduler import FleetSchedule


@dataclasses.dataclass(frozen=True)
class FleetRunnerConfig(RunnerConfig):
    """RunnerConfig + the fleet-specific knobs."""

    checkpoint_dir: str = "checkpoints/fleet"
    pipelined: bool = True        # False -> paper-synchronous semantics
    single_program: bool = True   # ONE compiled program per iteration
                                  # (False -> per-scenario dispatch path)
    bank_size: int = 17           # per-scenario initial-state bank
    traj_capacity: int = 2        # 2 == double buffering (pipeline minimum)
    metrics_capacity: int = 512   # device-resident metric history per scenario
    d_embed: int = 32             # shared-trunk width (multitask policy)
    n_shared_layers: int = 2


def _host_record(rec: dict) -> dict:
    """Drained metric record -> JSON-ready host values.

    Scalar metrics become Python floats; vector-valued metrics arrive from
    `broker.drain_host` as nested lists and pass through unchanged (a
    non-scalar leaf used to reach the former unconditional `float(v)` as a
    numpy array and crash the training loop at drain time).
    """
    return {key: v if isinstance(v, list) else float(v)
            for key, v in rec.items()}


class FleetOrchestrator:
    """Per-scenario sub-fleet orchestrators + the shared multitask policy."""

    def __init__(self, schedule: FleetSchedule, *, mesh=None, seed: int = 0,
                 bank_size: int = 17, d_embed: int = 32,
                 n_shared_layers: int = 2):
        self.schedule = schedule
        self.mcfg = multitask.MultiTaskConfig.from_envs(
            [(m.name, m.env) for m in schedule.members],
            d_embed=d_embed, n_shared_layers=n_shared_layers)
        # One core Orchestrator per scenario: same banks, sharding, and
        # jitted rollout programs as single-scenario training, with the
        # scenario's multitask head plugged in as the policy bundle.
        self.orchs = {
            m.name: Orchestrator(
                m.env, FleetConfig(n_envs=m.n_envs, bank_size=bank_size),
                mesh=mesh, seed=sched_lib.scenario_seed(seed, i),
                policy=multitask.policy_fns(self.mcfg, m.name))
            for i, m in enumerate(schedule.members)
        }

    @property
    def names(self) -> tuple[str, ...]:
        return self.schedule.names

    def sample_all(self, params: dict, keys: dict[str, jax.Array]
                   ) -> dict[str, ppo_lib.Trajectory]:
        """Dispatch every sub-fleet's rollout (one jitted program each);
        returns without blocking — the trajectories are in-flight arrays."""
        return {name: self.orchs[name].sample_fleet(params, keys[name])
                for name in self.names}

    def evaluate_all(self, params: dict) -> dict[str, float]:
        """Deterministic held-out-state episode per scenario (blocks)."""
        return {name: float(self.orchs[name].evaluate(params))
                for name in self.names}


class FleetRunner(RunnerBase):
    """Heterogeneous-fleet training with the Runner durability contract."""

    def __init__(self, schedule: FleetSchedule,
                 ppo_cfg: ppo_lib.PPOConfig | None = None,
                 run_cfg: FleetRunnerConfig | None = None, *, mesh=None):
        super().__init__(run_cfg or FleetRunnerConfig())
        cfg = self.run_cfg
        self.ppo_cfg = ppo_cfg or ppo_lib.PPOConfig()
        self.schedule = schedule
        self.forch = FleetOrchestrator(
            schedule, mesh=mesh, seed=cfg.seed, bank_size=cfg.bank_size,
            d_embed=cfg.d_embed, n_shared_layers=cfg.n_shared_layers)
        self.mcfg = self.forch.mcfg
        self.weights = {m.name: m.weight for m in schedule.members}

        key = jax.random.PRNGKey(cfg.seed)
        self.seed_key, init_key = jax.random.split(key)
        self.params = multitask.init(init_key, self.mcfg)
        self.opt_state = optim.adam_init(self.params)

        # donate the optimizer state: it aliases its own output, so both
        # moment generations never live at once (params are NOT donated —
        # the in-flight overlapped rollout still reads them)
        self._update = jax.jit(self._update_impl, donate_argnums=(1,))

        # broker rings sized from the abstract trajectory/stats shapes
        # (eval_shape: no rollout or update actually runs here)
        traj_templates = {
            name: jax.eval_shape(self.forch.orchs[name].sample_fleet,
                                 self.params, jax.random.PRNGKey(0))
            for name in self.forch.names}
        stats_template = jax.eval_shape(
            self._update_impl, self.params, self.opt_state, traj_templates,
            jnp.zeros((), jnp.int32))[2]
        self.broker = broker_lib.broker_init(
            traj_templates, traj_capacity=cfg.traj_capacity,
            metric_templates={"fleet": stats_template},
            metrics_capacity=cfg.metrics_capacity)

        # the single fleet program (the default iteration path): update k,
        # the shard_map-ped super-batch rollout k+1, and the broker pushes
        # compiled into one XLA dispatch (fleet/superbatch.py)
        self.program = (superbatch_lib.FleetProgram(
            self.forch, self.weights, self.ppo_cfg, mesh=mesh)
            if cfg.single_program else None)

    # --- jitted joint update --------------------------------------------------
    def _update_impl(self, params, opt_state, trajs, k):
        # in-graph non-finite guard rides inside the program: the pipelined
        # loop never syncs to inspect stats (core/runner.py makes the same
        # call on the host instead); shared with the single fleet program
        return superbatch_lib.guarded_fleet_update(
            params, opt_state, self.ppo_cfg, self.mcfg, trajs, self.weights,
            k)

    # --- checkpoint hooks -----------------------------------------------------
    def _state_tree(self) -> dict:
        return {"params": self.params, "opt": self.opt_state,
                "broker": self.broker}

    def _load_state(self, tree: dict, manifest: dict) -> None:
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.broker = tree["broker"]
        self.iteration = int(manifest["meta"]["iteration"])

    def _checkpoint_meta(self) -> dict:
        # scenarios + trunk hyperparameters make the checkpoint
        # self-describing for the serving loader (repro.serve.load_policy
        # rebuilds the MultiTaskConfig from this meta alone; older
        # checkpoints without the trunk fields fall back to shape inference)
        return {**super()._checkpoint_meta(),
                "scenarios": list(self.forch.names),
                "n_envs": {m.name: m.n_envs for m in self.schedule.members},
                "pipelined": self.run_cfg.pipelined,
                "d_embed": self.run_cfg.d_embed,
                "n_shared_layers": self.run_cfg.n_shared_layers}

    # --- key bookkeeping ------------------------------------------------------
    def _keys(self, k: int) -> dict[str, jax.Array]:
        return {name: sched_lib.rollout_key(self.seed_key, i, k)
                for i, name in enumerate(self.forch.names)}

    # --- iteration bodies -----------------------------------------------------
    def _push_all(self, trajs: dict, stats) -> None:
        for name, traj in trajs.items():
            self.broker = self.broker._replace(traj={
                **self.broker.traj,
                name: broker_lib.push_donated(self.broker.traj[name], traj)})
        if stats is not None:
            self.broker = self.broker._replace(metrics={
                **self.broker.metrics,
                "fleet": broker_lib.push_donated(self.broker.metrics["fleet"],
                                                 stats)})

    def run_iteration_pipelined(self, k: int) -> None:
        """Dispatch-only iteration: consume traj_k from the broker, overlap
        rollout k+1 with update k, park the results back in the broker.

        Default (`single_program`): ONE compiled program carries all of it
        — XLA schedules the dependency-free update-k / rollout-(k+1)
        subgraphs concurrently, and a straggling scenario inside the
        super-batch only delays its own rows, not a whole dispatch.

        Dispatch fallback: both programs read `params_k`; the update is
        ENQUEUED first so that a strictly in-order backend retires
        params_{k+1} without waiting on rollout k+1 — the next rollout is
        always the computation left in flight when the host runs ahead
        (steady-state double buffering).

        Either way the host work is the span `fleet.dispatch` (repro.obs).
        """
        with obs.span("fleet.dispatch"):
            if self.program is not None:
                self.params, self.opt_state, self.broker = self.program.step(
                    self.params, self.opt_state, self.broker,
                    jnp.asarray(k, jnp.int32), self._keys(k + 1))
                return
            params_k = self.params
            trajs_k = {name: broker_lib.latest_traj(self.broker, name)
                       for name in self.forch.names}
            self.params, self.opt_state, stats = self._update(
                params_k, self.opt_state, trajs_k, jnp.asarray(k, jnp.int32))
            next_trajs = self.forch.sample_all(params_k, self._keys(k + 1))
            self._push_all(next_trajs, stats)

    def run_iteration_sync(self, k: int) -> dict:
        """Paper-synchronous iteration: sample -> block -> update -> block,
        with the per-iteration host metrics readback core/runner.py does.
        The strict on-policy mode, and the benchmark baseline."""
        t0 = time.perf_counter()
        trajs = self.forch.sample_all(self.params, self._keys(k))
        trajs = jax.block_until_ready(trajs)
        t_sample = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.params, self.opt_state, stats = self._update(
            self.params, self.opt_state, dict(trajs),
            jnp.asarray(k, jnp.int32))
        host_stats = jax.device_get(stats)  # blocks: the sync-mode contract
        t_update = time.perf_counter() - t0
        self._push_all(trajs, stats)
        return {"iteration": k, "t_sample_s": t_sample,
                "t_update_s": t_update,
                **{name: float(v) for name, v in host_stats.items()}}

    # --- training -------------------------------------------------------------
    def train(self, n_iterations: int | None = None, *,
              resume: bool = True) -> list[dict]:
        """Run until `n_iterations`; returns this call's per-iteration
        metric records (drained from the device ring at the end)."""
        cfg = self.run_cfg
        total = n_iterations or cfg.n_iterations
        if resume:
            self.restore()
        head_start = int(jax.device_get(self.broker.metrics["fleet"].head))
        timings: list[dict] = []

        # pipeline prologue: the broker must hold traj_0 before update 0
        if cfg.pipelined and int(jax.device_get(
                self.broker.traj[self.forch.names[0]].head)) == 0:
            if self.program is not None:
                self.broker = self.program.prologue(
                    self.params, self.broker, self._keys(0))
            else:
                self._push_all(
                    self.forch.sample_all(self.params, self._keys(0)), None)

        while self.iteration < total:
            k = self.iteration
            if cfg.pipelined:
                self.run_iteration_pipelined(k)
            else:
                timings.append(self.run_iteration_sync(k))
            self.iteration = k + 1
            if (k + 1) % cfg.eval_every == 0:
                evals = self.forch.evaluate_all(self.params)  # blocks (cadenced)
                self._log({"iteration": k,
                           **{f"{n}/eval_return_norm": v
                              for n, v in evals.items()}})
            if (k + 1) % cfg.checkpoint_every == 0:
                self.save_checkpoint()
        self.save_checkpoint(block=True)
        self.join_pending_checkpoint()

        # drain this call's device-resident metrics into the jsonl stream
        head_end = int(jax.device_get(self.broker.metrics["fleet"].head))
        n_new = head_end - head_start
        drained = broker_lib.drain_host(self.broker)["fleet"]
        # the ring only holds metrics_capacity records: a longer call loses
        # the oldest ones — say so instead of silently under-reporting
        records = drained[-n_new:] if n_new > 0 else []
        if n_new > len(records):
            self._log({"dropped_metric_records": n_new - len(records),
                       "metrics_capacity": cfg.metrics_capacity})
        timing_by_iter = {t["iteration"]: t for t in timings}
        history = []
        for rec in records:
            rec = _host_record(rec)
            for name in self.forch.names:
                n_steps = self.forch.orchs[name].env.n_actions
                rec[f"{name}/return_norm"] = (
                    rec[f"{name}/mean_return"] / n_steps)
            # sync-mode host timings, matched by iteration (records may be
            # a ring-bounded suffix, so positional pairing would misalign)
            rec.update(timing_by_iter.get(int(rec["iteration"]), {}))
            self._log(rec)
            history.append(rec)
        return history


def make_fleet_runner(names, total_envs: int = 6, *,
                      ppo_cfg: ppo_lib.PPOConfig | None = None,
                      run_cfg: FleetRunnerConfig | None = None,
                      mesh=None, costs: dict[str, float] | None = None,
                      **schedule_kwargs) -> FleetRunner:
    """Convenience: registry names -> schedule -> FleetRunner."""
    from .. import envs

    schedule = sched_lib.build_schedule(
        [(n, envs.make(n)) for n in names], total_envs, costs=costs,
        **schedule_kwargs)
    return FleetRunner(schedule, ppo_cfg=ppo_cfg, run_cfg=run_cfg, mesh=mesh)
