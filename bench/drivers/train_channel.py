"""Training cell of the wall-modelled channel: a fleet of channel WMLES
environments trained by the pipelined `FleetRunner`, one compiled
`FleetProgram.step` per PPO iteration.

The loop is bench/drivers/train.py's (whose runner, install, warm-up and
window helpers it calls): set-up builds the runner from the configuration
file, installs the benchmark's bank and the seed's weights, rolls the
prologue and the first `steps_compared` iterations; the window keeps the
runner's one-iteration run-ahead; after it, the plain reference
(bench/reference/channel_les.py) follows the same first iterations from the
same weights, bank rows and action noise.  The rollout's guard reverts are
read from the program's metrics record (`<scenario>/reverted_envs`).

A program without the planar wall path (`fused_wall_rhs`) is refused at
once: its channel would step the natural layout, for many minutes.

Traffic keys: n_envs, steps_compared, reference_block (envs per reference
block).

    python3 bench/drivers/train_channel.py --workload <cell> --seeds 1 2 3

prints the control's and the planted faults' readings per seed (what
bench/control.py prints for the HIT cells), on the chip this process finds.
"""
from __future__ import annotations

import gc
import math
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_root), str(_root / "src")]

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench.common import Check, Outcome, stderr
from bench.counts import channel as counts
from bench.drivers import train
from bench.reference import ppo as ref_ppo
from bench.reference import training as ref_training
from bench.reference.channel_les import ChannelReference

KERNEL = "fused_wall_rhs"


def require_planar_wall_path() -> None:
    from repro.kernels import rhs
    if not hasattr(rhs, "fused_wall_rhs_planar"):
        raise RuntimeError(
            "the program has no planar wall RHS (kernels/rhs.py "
            "fused_wall_rhs_planar): its channel steps the natural layout, "
            "which this cell does not measure")


def program_config(config: dict) -> dict:
    """The configuration with its physics as the program's frozen config
    takes it (tuples, not lists)."""
    phys = {k: tuple(v) if isinstance(v, list) else v
            for k, v in config["physics"].items()}
    return dict(config, physics=phys)


def make_inputs(config: dict, seed_key):
    """(reference, bank, weights, rollout key) of one run.  The initial
    policy's mean action (`a_mean`, 1.0: the equilibrium wall model), its
    spread over elements (`head_scale`) and its std are the
    configuration's `initial_policy`."""
    ref = ChannelReference(config["physics"])
    a = config["assumed"]
    pol = a["initial_policy"]
    bank = ref.bank(jax.random.PRNGKey(a["bank_seed"]), a["bank_size"])
    k_w, k_run = jax.random.split(seed_key)

    def init(key):
        return ref_ppo.init_params(
            key, config["registry"], 3 * ref.n**3, a["d_embed"],
            a["n_shared_layers"], log_std=math.log(pol["a_std"]),
            mean_share=pol["a_mean"] / config["physics"]["a_max"],
            head_scale=pol["head_scale"])

    return ref, bank, jax.jit(init)(k_w), k_run


def build(config: dict, n_envs: int, seed_key, workdir: str,
          **env_overrides):
    """(reference, bank, weights, rollout key, installed runner)."""
    ref, bank, params, run_key = make_inputs(config, seed_key)
    runner = train.build_runner(program_config(config), n_envs, workdir,
                                **env_overrides)
    train.install(runner, config, bank, params, run_key)
    return ref, bank, params, run_key, runner


def run(h) -> Outcome:
    from repro.fleet import broker as broker_lib

    require_planar_wall_path()
    config, traffic = h.config, h.traffic
    name = config["registry"]
    n_envs, steps = traffic["n_envs"], traffic["steps_compared"]
    marks = {"start": time.perf_counter()}
    ref, bank, params, run_key, runner = build(config, n_envs, h.seed_key,
                                               h.tmp)
    marks["built"] = time.perf_counter()
    got = train.first_iterations(runner, steps)
    marks["first_iterations"] = time.perf_counter()

    compiles0 = h.clock.count
    with h.profile():
        t0, done = train.window(runner, h.seconds, h.spans)
    compiles = h.clock.count - compiles0
    memory_peak = h.memory_peak()
    if not done:
        raise RuntimeError(f"no iteration finished within {h.seconds} s")

    records = {int(r["iteration"]): r for r in
               broker_lib.drain_host(runner.broker)["fleet"]}
    nan = {"update_ok": 0.0, "loss": math.nan,
           f"{name}/mean_return": math.nan}   # an iteration that left none
    records = [records.get(k, nan) for k in range(steps + len(done))]
    window_records = records[steps:]
    failed = sum(1 for r in window_records
                 if r["update_ok"] != 1.0
                 or not math.isfinite(r[f"{name}/mean_return"]))
    reverted = [r.get(f"{name}/reverted_envs") for r in window_records]
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
    rhs_flops, rhs_bytes = counts.rhs_call(
        ref.n, (ref.Kx, ref.Ky, ref.Kz), config["physics"]["wm_iters"],
        n_envs)
    context = {"iterations": len(done), "elapsed_s": elapsed,
               "rhs_call_flops": rhs_flops, "rhs_call_bytes": rhs_bytes,
               "rhs_kernel": KERNEL}
    if None not in reverted:
        context.update(reverted_env_steps=float(sum(reverted)),
                       window_env_steps=float(per_iter * len(done)))
    return Outcome(
        attempted=len(done), failed=failed,
        e2e={"env_steps_per_s": per_iter * len(done) / elapsed},
        checks=checks, memory_peak_bytes=memory_peak, context=context,
        notes={"iteration_s": np.diff([t0] + done).tolist(),
               "build_s": marks["built"] - marks["start"],
               "first_iterations_s": marks["first_iterations"]
               - marks["built"],
               "reference_s": marks["reference"], "gaps": gaps,
               "reverted_env_steps": reverted,
               "worst_leaves": compare.worst_leaves(
                   m_got=got["m_first"], m_want=want["opt_first"].m,
                   p0=params, p_got=got["p_last"],
                   p_want=want["params"][steps])})


def readings(config: dict, traffic: dict, seed: int, workdir: str) -> dict:
    """The control's and the planted faults' gaps at one seed: the
    program's own bfloat16 rollout path driven through the first
    iterations, the reference's update on half the batch and on one
    altered reward, and a step that returns its state unchanged."""
    from bench import common
    from bench.control import _updates
    from repro.fleet import broker as broker_lib

    name = config["registry"]
    n_envs, steps = traffic["n_envs"], traffic["steps_compared"]
    ref, bank, params, run_key, runner = build(
        config, n_envs, common.seed_key(seed), workdir, precision="bf16")
    got = train.first_iterations(runner, steps)
    records = {int(r["iteration"]): r for r in
               broker_lib.drain_host(runner.broker)["fleet"]}
    losses_bf16 = [records[k]["loss"] for k in range(steps)]
    del runner
    gc.collect()

    want = ref_training.follow(ref, params, name, bank, run_key,
                               n_envs=n_envs, steps=steps,
                               block=traffic["reference_block"])

    def gaps(losses, m_first, p_last):
        return compare.training_gaps(
            losses_got=losses, losses_want=want["losses"], m_got=m_first,
            m_want=want["opt_first"].m, p0=params, p_got=p_last,
            p_want=want["params"][steps])

    trajs = want["trajs"][:steps]
    half = [jax.tree.map(lambda x: x[:n_envs // 2] if x.ndim == 1
                         else x[:, :n_envs // 2], t) for t in trajs]
    altered = [dict(t) for t in trajs]
    altered[0]["rewards"] = altered[0]["rewards"].at[0, 0].multiply(-1.0)
    a_max = config["physics"]["a_max"]
    out = {"seed": seed,
           "control_bf16": gaps(losses_bf16, got["m_first"], got["p_last"]),
           "state_unchanged": gaps(losses_bf16, jax.tree.map(
               jnp.zeros_like, got["m_first"]), params)}
    for label, tr in (("half_batch", half), ("reward_altered", altered)):
        u = _updates(want, tr, name, a_max)
        out[label] = gaps(u["losses"], u["m_first"], u["p_last"])
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    from bench import common

    ap = argparse.ArgumentParser(description="control and fault readings "
                                 "of a channel training cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = common.load_json(common.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    config = common.load_json(common.config_path(cell["config"]))
    traffic = common.load_json(common.traffic_path(cell["traffic"]))
    jax.config.update("jax_default_matmul_precision", "highest")
    require_planar_wall_path()
    for seed in args.seeds:
        print(json.dumps(readings(config, traffic, seed,
                                  tempfile.mkdtemp())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
