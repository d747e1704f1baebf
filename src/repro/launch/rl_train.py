"""The paper's training loop: PPO on any registered environment (Relexi).

This is the production entry point for the RL-CFD cells — the TPU-native
equivalent of the paper's `relexi --config ...` SLURM job.  The scenario is
selected by registry name (`repro.envs`); the fleet shards over the mesh's
`data` axis (every device) and the spec-built policy trains with clip-PPO
using the paper's hyperparameters (Sec. 5.3).

A run that resumes from `--checkpoint-dir` continues to `--iterations`.
The command exits non-zero when the run was not clean: an iteration was
retried, a non-finite update was skipped, a return was non-finite, or no
iteration ran (a checkpoint directory already at `--iterations`).

    # paper 24-DOF HIT configuration, 16 parallel environments:
    PYTHONPATH=src python -m repro.launch.rl_train --env hit_les_24dof \
        --n-envs 16 --iterations 4000
    # the 1-D Burgers control scenario, same loop:
    PYTHONPATH=src python -m repro.launch.rl_train --env burgers_96dof
    # CPU-scale smoke:
    PYTHONPATH=src python -m repro.launch.rl_train --reduced --n-envs 2 \
        --iterations 3
"""
from __future__ import annotations

import argparse

from .. import envs
from ..core.orchestrator import FleetConfig
from ..core.ppo import PPOConfig
from ..core.runner import Runner, RunnerConfig, read_metrics, run_faults
from . import mesh as mesh_lib
from .compile_cache import enable_compile_cache


def main(argv: list[str] | None = None) -> list[dict]:
    """Parse `argv`, train, and return this run's per-iteration records;
    raises SystemExit(1) when the run was not clean (module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default=None, choices=envs.registered(),
                    help="registered environment name")
    ap.add_argument("--dof", type=int, choices=(24, 32), default=24,
                    help="HIT Table-1 scale (when --env is not given)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale HIT config (when --env is not given)")
    ap.add_argument("--n-envs", type=int, default=16,
                    help="parallel environments (paper: 16/32/64)")
    ap.add_argument("--iterations", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--checkpoint-dir", default="checkpoints/relexi")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-mesh", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.env:
        name = args.env
    elif args.reduced:
        name = "hit_les_reduced"
    else:
        name = f"hit_les_{args.dof}dof"
    env = envs.make(name)

    mesh = None if args.no_mesh else mesh_lib.make_fleet_mesh()
    fleet = FleetConfig(n_envs=args.n_envs,
                        bank_size=max(args.n_envs + 1, 9))
    runner = Runner(
        env, fleet,
        ppo_cfg=PPOConfig(),  # paper Sec. 5.3 defaults
        run_cfg=RunnerConfig(
            n_iterations=args.iterations,
            eval_every=args.eval_every,
            checkpoint_every=args.checkpoint_every,
            checkpoint_dir=args.checkpoint_dir,
            seed=args.seed,
        ),
        mesh=mesh,
    )
    print(f"training {name}: {args.iterations} iterations x {args.n_envs} envs")
    n_logged = len(read_metrics(runner.metrics_path))
    runner.restore()
    expected = max(1, args.iterations - runner.iteration)
    history = runner.train(resume=False)
    last = history[-1] if history else {}
    print(f"finished {len(history)} iterations; "
          f"final return={last.get('return_norm', float('nan')):.4f}")
    faults = run_faults(read_metrics(runner.metrics_path)[n_logged:],
                        expected)
    if faults:
        raise SystemExit("training run not clean: " + "; ".join(faults))
    return history


if __name__ == "__main__":
    main()
