"""Training cells: a fleet of HIT-LES environments trained by the pipelined
`FleetRunner`, one compiled `FleetProgram.step` per PPO iteration.

Set-up builds one runner from the configuration file, installs the
benchmark's initial-state bank and its weights from the seed, rolls the
prologue and drives the first `steps_compared` iterations through
`FleetRunner.run_iteration_pipelined`.  The window then keeps calling it
with the runner's own one-iteration run-ahead: iteration k+1 is dispatched
before the host waits for iteration k.  Once the window has closed, the
program's state is freed and the plain reference follows the same first
iterations from the same weights, bank rows and action noise.

Traffic keys: n_envs, steps_compared, reference_block (envs per reference
block).
"""
from __future__ import annotations

import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench.common import Check, Outcome, stderr
from bench.counts import hit as counts
from bench.reference import ppo as ref_ppo
from bench.reference import training as ref_training
from bench.reference.hit_les import HITReference


def build_runner(config: dict, n_envs: int, workdir: str, **env_overrides):
    """The FleetRunner of one HIT configuration at `n_envs` environments on
    a one-`data`-shard mesh over every local device."""
    from repro import envs
    from repro.fleet import scheduler
    from repro.fleet.pipeline import FleetRunner, FleetRunnerConfig
    from repro.launch import mesh as mesh_lib

    name = config["registry"]
    env = envs.make(name, **config["physics"], **env_overrides)
    sched = scheduler.build_schedule([(name, env)], n_envs,
                                     use_artifacts=False)
    a = config["assumed"]
    run_cfg = FleetRunnerConfig(
        checkpoint_dir=workdir, async_checkpoint=False,
        bank_size=a["bank_size"], d_embed=a["d_embed"],
        n_shared_layers=a["n_shared_layers"])
    return FleetRunner(sched, run_cfg=run_cfg,
                       mesh=mesh_lib.make_fleet_mesh())


def make_inputs(config: dict, seed_key):
    """(reference, bank, weights, rollout key) of one run: the bank is the
    configuration's fixed set of initial states, the weights and the
    rollout key come from the seed.  The initial policy's mean C_s, its
    spread over elements (`head_scale`) and its std are the
    configuration's `initial_policy`: a range in which no element's C_s
    falls to where the solver blows up."""
    ref = HITReference(config["physics"])
    a = config["assumed"]
    pol = a["initial_policy"]
    bank = ref.bank(jax.random.PRNGKey(a["bank_seed"]), a["bank_size"])
    k_w, k_run = jax.random.split(seed_key)
    init = functools.partial(
        ref_ppo.init_params, log_std=math.log(pol["cs_std"]),
        mean_share=pol["cs_mean"] / config["physics"]["cs_max"],
        head_scale=pol["head_scale"])
    params = jax.jit(init, static_argnums=(1, 2, 3, 4))(
        k_w, config["registry"], 3 * ref.n**3, a["d_embed"],
        a["n_shared_layers"])
    return ref, bank, params, k_run


def install(runner, config: dict, bank, params, run_key) -> None:
    """Hand the benchmark's bank, weights and rollout key to the runner."""
    from repro import optim

    name = config["registry"]
    have = jax.tree.structure(runner.params)
    if have != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(runner.params),
                                               jax.tree.leaves(params))):
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree: {have}")
    orch = runner.forch.orchs[name]
    if orch.bank.shape != bank.shape:
        raise ValueError(f"bank {bank.shape} != program's {orch.bank.shape}")
    orch.bank = bank
    runner.params = params
    runner.opt_state = optim.adam_init(params)
    runner.seed_key = run_key


def first_iterations(runner, steps: int) -> dict:
    """Prologue and the first `steps` iterations through the window's own
    call; returns what the reference is compared with."""
    p0 = runner.params
    runner.broker = runner.program.prologue(runner.params, runner.broker,
                                            runner._keys(0))
    m_first = None
    for k in range(steps):
        runner.run_iteration_pipelined(k)
        if k == 0:   # the next iteration donates the optimizer state
            m_first = jax.tree.map(jnp.copy, runner.opt_state.m)
    runner.iteration = steps
    jax.block_until_ready((runner.params, m_first))
    return {"p0": p0, "m_first": m_first, "p_last": runner.params}


def window(runner, seconds: float, spans) -> tuple[float, list[float]]:
    """Iterations until `seconds` have passed, one dispatched ahead of the
    one the host waits for.  Returns (window start, completion times of
    the iterations that finished inside the window)."""
    done: list[float] = []
    t0 = time.perf_counter()
    with spans("bench.window"):
        with spans("iteration.dispatch"):
            runner.run_iteration_pipelined(runner.iteration)
        runner.iteration += 1
        inflight = runner.params
        while True:
            nxt = None
            if time.perf_counter() - t0 < seconds:
                with spans("iteration.dispatch"):
                    runner.run_iteration_pipelined(runner.iteration)
                runner.iteration += 1
                nxt = runner.params
            with spans("iteration.wait"):
                jax.block_until_ready(inflight)
            t = time.perf_counter()
            if t - t0 <= seconds:
                done.append(t)
            if nxt is None:
                break
            inflight = nxt
    return t0, done


def run(h) -> Outcome:
    from repro.fleet import broker as broker_lib

    config, traffic = h.config, h.traffic
    name = config["registry"]
    n_envs, steps = traffic["n_envs"], traffic["steps_compared"]
    marks = {"start": time.perf_counter()}
    ref, bank, params, run_key = make_inputs(config, h.seed_key)
    runner = build_runner(config, n_envs, h.tmp)
    install(runner, config, bank, params, run_key)
    marks["built"] = time.perf_counter()
    got = first_iterations(runner, steps)
    marks["first_iterations"] = time.perf_counter()

    compiles0 = h.clock.count
    with h.profile():
        t0, done = window(runner, h.seconds, h.spans)
    compiles = h.clock.count - compiles0
    memory_peak = h.memory_peak()
    if not done:
        raise RuntimeError(f"no iteration finished within {h.seconds} s")

    records = {int(r["iteration"]): r for r in
               broker_lib.drain_host(runner.broker)["fleet"]}
    nan = {"update_ok": 0.0, "loss": math.nan,
           f"{name}/mean_return": math.nan}   # an iteration that left none
    records = [records.get(k, nan) for k in range(steps + len(done))]
    failed = sum(1 for r in records[steps:]
                 if r["update_ok"] != 1.0
                 or not math.isfinite(r[f"{name}/mean_return"]))
    losses_got = [r["loss"] for r in records[:steps]]
    del runner
    gc.collect()

    t_ref = time.perf_counter()
    want = ref_training.follow(ref, params, name, bank, run_key,
                               n_envs=n_envs, steps=steps,
                               block=traffic["reference_block"])
    gaps = compare.training_gaps(
        losses_got=losses_got, losses_want=want["losses"],
        m_got=got["m_first"], m_want=want["opt_first"].m, p0=params,
        p_got=got["p_last"], p_want=want["params"][steps])
    marks["reference"] = time.perf_counter() - t_ref
    stderr(f"reference: {marks['reference']:.1f} s; losses "
           f"program {losses_got} reference {want['losses']}")
    checks = [Check(k, v, h.limits[k]) for k, v in gaps.items()]
    checks.append(Check("compiles_in_window", float(compiles), 0.0))

    elapsed = done[-1] - t0
    per_iter = n_envs * ref.n_actions
    iter_flops = counts.train_iteration_flops(
        n=ref.n, k=ref.K, n_envs=n_envs, n_actions=ref.n_actions,
        n_substeps=ref.n_substeps, d_embed=config["assumed"]["d_embed"],
        n_shared=config["assumed"]["n_shared_layers"])
    rhs_flops, rhs_bytes = counts.rhs_call(ref.n, ref.K, n_envs)
    return Outcome(
        attempted=len(done), failed=failed,
        e2e={"env_steps_per_s": per_iter * len(done) / elapsed},
        checks=checks, memory_peak_bytes=memory_peak,
        context={"iterations": len(done), "elapsed_s": elapsed,
                 "iteration_flops": iter_flops,
                 "rhs_call_flops": rhs_flops, "rhs_call_bytes": rhs_bytes,
                 "rhs_kernel": "fused_ns_rhs"},
        notes={"iteration_s": np.diff([t0] + done).tolist(),
               "build_s": marks["built"] - marks["start"],
               "first_iterations_s": marks["first_iterations"]
               - marks["built"],
               "reference_s": marks["reference"], "gaps": gaps,
               "worst_leaves": compare.worst_leaves(
                   m_got=got["m_first"], m_want=want["opt_first"].m,
                   p0=params, p_got=got["p_last"],
                   p_want=want["params"][steps])})
