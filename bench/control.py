"""Readings that set the upper end of each limit: the control and planted
faults, at a cell's own size, on the chip this process finds.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

Training cells: the control is the program's own bfloat16 rollout path
(`precision="bf16"`, the nearest precision below the configuration's
float32) driven through the same first iterations as a benchmark run and
compared with the float32 reference.  The faults are planted in the
reference's update, the rollouts kept: half of the batch left out (the
mean taken over the rest), and one reward altered where the rollout
produced it.  A step that returns its state unchanged reads 1 on
`step_gap` by construction and needs no run.
Serving cells: the control is the reference computed in bfloat16 (weights
and observations), and the fault one altered answer.

Prints one JSON line per seed.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import common, compare  # noqa: E402
from bench.reference import ppo as ref_ppo  # noqa: E402
from bench.reference import training as ref_training  # noqa: E402


def _updates(want: dict, trajs: list, name: str, cs_max: float) -> dict:
    """The reference's updates over given trajectories from params_0."""
    params = [want["params"][0]]
    opts = [ref_ppo.adam_init(params[0])]
    losses = []
    with jax.default_matmul_precision("highest"):
        for traj in trajs:
            p, o, loss = ref_training._update(params[-1], opts[-1], traj,
                                              name, cs_max,
                                              ref_ppo.PPOSettings())
            params.append(p)
            opts.append(o)
            losses.append(float(loss))
    return {"losses": losses, "m_first": opts[1].m, "p_last": params[-1]}


def train_readings(config: dict, traffic: dict, seed: int, workdir: str
                   ) -> dict:
    from bench.drivers import train

    name = config["registry"]
    n_envs, steps = traffic["n_envs"], traffic["steps_compared"]
    ref, bank, params, run_key = train.make_inputs(config,
                                                   common.seed_key(seed))
    runner = train.build_runner(config, n_envs, workdir, precision="bf16")
    train.install(runner, config, bank, params, run_key)
    got = train.first_iterations(runner, steps)
    from repro.fleet import broker as broker_lib
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
    cs_max = config["physics"]["cs_max"]
    out = {"seed": seed,
           "control_bf16": gaps(losses_bf16, got["m_first"], got["p_last"]),
           "state_unchanged": gaps(losses_bf16, jax.tree.map(
               jnp.zeros_like, got["m_first"]), params)}
    for label, tr in (("half_batch", half), ("reward_altered", altered)):
        u = _updates(want, tr, name, cs_max)
        out[label] = gaps(u["losses"], u["m_first"], u["p_last"])
    return out


def serve_readings(config: dict, traffic: dict, seed: int) -> dict:
    from bench.drivers import serve
    from bench.reference.hit_les import HITReference

    name = config["registry"]
    a = config["assumed"]
    ref = HITReference(config["physics"])
    params = ref_ppo.init_params(common.seed_key(seed), name, 3 * ref.n**3,
                                 a["d_embed"], a["n_shared_layers"])
    pool = serve.make_pool(ref, config, traffic["pool_size"])
    act, val = serve.reference_answers(config, params, pool)
    bf = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    feats = ref_ppo.features(jnp.asarray(pool, jnp.bfloat16))
    cs_max = config["physics"]["cs_max"]
    act_bf = np.asarray(ref_ppo.actor_mean(bf, name, feats, cs_max),
                        np.float64)
    val_bf = np.asarray(ref_ppo.value(bf, name, feats), np.float64)
    picks = np.arange(len(pool))
    control = serve.answer_gaps(
        {i: (act_bf[i], val_bf[i]) for i in picks}, picks, act, val, cs_max)
    wrong = act[0].copy()
    wrong[0] = np.mean(wrong)
    fault = serve.answer_gaps({0: (wrong, val[0])}, picks, act, val, cs_max)
    return {"seed": seed, "control_bf16": control, "answer_altered": fault}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = common.load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    config = common.load_json(common.config_path(cell["config"]))
    traffic = common.load_json(common.traffic_path(cell["traffic"]))
    jax.config.update("jax_default_matmul_precision", "highest")
    for seed in args.seeds:
        if traffic["driver"] == "train":
            out = train_readings(config, traffic, seed, tempfile.mkdtemp())
        else:
            out = serve_readings(config, traffic, seed)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
