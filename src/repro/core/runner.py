"""Fault-tolerant RL training runner (paper Algorithm 1, production-hardened).

Determinism contract
--------------------
Iteration k is a pure function of (seed, k, params_k, opt_k): the rollout key
is `fold_in(seed_key, k)` and initial states are drawn from the device bank.
Consequences for a 1000-node fleet:

  * node failure      -> resume from the newest complete checkpoint and
                         re-execute iterations deterministically (no
                         divergence between the original and replayed run);
  * straggler shards  -> the fleet program is bulk-synchronous SPMD; there is
                         no per-environment scheduling to go astray.  Slow
                         *hosts* (data feeding, checkpoint writes) are taken
                         off the critical path: checkpoints are written by a
                         background thread from host copies;
  * elastic restart   -> `Runner.restore` re-places the state on the current
                         mesh (core/elastic.py) and adjusts the fleet size.

A `failure_injector` hook (tests) raises mid-iteration to exercise the
recovery path.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .. import optim
from ..envs.base import Env
from . import checkpoints, policy as policy_lib, ppo as ppo_lib
from .orchestrator import FleetConfig, Orchestrator


@dataclasses.dataclass(frozen=True)
class RunnerConfig:
    n_iterations: int = 100
    eval_every: int = 10          # paper: test state evaluated every 10 iters
    checkpoint_every: int = 25
    checkpoint_dir: str = "checkpoints/relexi"
    metrics_path: str | None = None  # jsonl; default <ckpt_dir>/metrics.jsonl
    keep_checkpoints: int = 3
    seed: int = 0
    async_checkpoint: bool = True


def read_metrics(path: str) -> list[dict]:
    """All records of a jsonl metrics stream ([] if it does not exist)."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_faults(records: list[dict], expected: int,
               return_keys: tuple[str, ...] = ("return_norm",)) -> list[str]:
    """Why a training run's metric records do not show a clean run.

    The loops recover from faults on their own — `Runner.train` retries a
    failed iteration and both loops keep the old params when an update is
    non-finite — so a run can finish while its numbers are not those of a
    clean run.  Entry points that must not pass silently (`rl_train`,
    `chip_smoke.py`) fail on any retry, any skipped or reverted update, a
    non-finite return, or fewer than `expected` iteration records (those
    carrying one of `return_keys`).
    """
    faults = []
    ran = 0
    for rec in records:
        it = rec.get("iteration")
        if "retry" in rec:
            faults.append(f"iteration {it}: retry {rec['retry']} after "
                          f"{rec.get('error', '')!r}")
        if rec.get("skipped_nonfinite_update") or rec.get("update_ok") == 0.0:
            faults.append(f"iteration {it}: non-finite update skipped")
        values = [rec[k] for k in return_keys if k in rec]
        if values:
            ran += 1
            if not all(isinstance(v, (int, float)) and math.isfinite(v)
                       for v in values):
                faults.append(f"iteration {it}: non-finite return {values}")
    if ran < expected:
        faults.append(f"{ran} of {expected} iterations ran")
    return faults


class RunnerBase:
    """Checkpoint + metrics plumbing shared by training loops.

    The single-scenario `Runner` below and the multi-scenario fleet runner
    (`fleet/pipeline.py`) carry different state trees (one policy vs. the
    multitask tree + broker rings) but share the same durability contract:
    atomic versioned checkpoints written off the critical path by a
    background thread, template-based restore, and a jsonl metrics stream.
    Subclasses define `_state_tree` / `_load_state` / `_checkpoint_meta`.
    """

    run_cfg: RunnerConfig

    def __init__(self, run_cfg: RunnerConfig | None):
        self.run_cfg = run_cfg or RunnerConfig()
        self.iteration = 0
        self._ckpt_thread: threading.Thread | None = None
        self.metrics_path = self.run_cfg.metrics_path or os.path.join(
            self.run_cfg.checkpoint_dir, "metrics.jsonl")

    # --- subclass hooks -------------------------------------------------------
    def _state_tree(self) -> dict:
        """The checkpointed device state (template for restore)."""
        raise NotImplementedError

    def _load_state(self, tree: dict, manifest: dict) -> None:
        """Install a restored state tree + manifest onto self."""
        raise NotImplementedError

    def _checkpoint_meta(self) -> dict:
        return {"iteration": self.iteration, "seed": self.run_cfg.seed}

    # --- checkpoint plumbing --------------------------------------------------
    def save_checkpoint(self, block: bool = False) -> None:
        tree = jax.device_get(self._state_tree())  # host copy off critical path
        meta = self._checkpoint_meta()
        step = self.iteration

        def write():
            checkpoints.save(self.run_cfg.checkpoint_dir, step, tree,
                             meta=meta, keep=self.run_cfg.keep_checkpoints)

        self.join_pending_checkpoint()  # never two concurrent writers
        if self.run_cfg.async_checkpoint and not block:
            self._ckpt_thread = threading.Thread(target=write, daemon=True)
            self._ckpt_thread.start()
        else:
            write()

    def join_pending_checkpoint(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None

    def restore(self) -> bool:
        """Resume from the newest complete checkpoint; returns True if found."""
        step = checkpoints.latest_step(self.run_cfg.checkpoint_dir)
        if step is None:
            return False
        tree, manifest = checkpoints.restore(
            self.run_cfg.checkpoint_dir, step, self._state_tree())
        self._load_state(tree, manifest)
        return True

    # --- metrics ---------------------------------------------------------------
    def _log(self, record: dict) -> None:
        os.makedirs(os.path.dirname(self.metrics_path) or ".", exist_ok=True)
        with open(self.metrics_path, "a") as f:
            f.write(json.dumps(record) + "\n")


class Runner(RunnerBase):
    def __init__(
        self,
        env: Env,
        fleet: FleetConfig,
        ppo_cfg: ppo_lib.PPOConfig | None = None,
        run_cfg: RunnerConfig | None = None,
        *,
        mesh=None,
        failure_injector: Callable[[int], None] | None = None,
    ):
        super().__init__(run_cfg)
        self.ppo_cfg = ppo_cfg or ppo_lib.PPOConfig()
        self.orch = Orchestrator(env, fleet, mesh=mesh, seed=self.run_cfg.seed)
        self.failure_injector = failure_injector

        key = jax.random.PRNGKey(self.run_cfg.seed)
        self.seed_key, init_key = jax.random.split(key)
        self.params = policy_lib.init(init_key, self.orch.pcfg)
        self.opt_state = optim.adam_init(self.params)

        self._update = jax.jit(
            lambda p, o, t: ppo_lib.update(p, o, self.ppo_cfg, self.orch.pcfg, t)
        )

    # --- checkpoint hooks -----------------------------------------------------
    def _state_tree(self) -> dict:
        return {"params": self.params, "opt": self.opt_state}

    def _load_state(self, tree: dict, manifest: dict) -> None:
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.iteration = int(manifest["meta"]["iteration"])

    def _checkpoint_meta(self) -> dict:
        return {**super()._checkpoint_meta(), "n_envs": self.orch.fleet.n_envs}

    # --- training ---------------------------------------------------------------
    def run_iteration(self, k: int) -> dict:
        """One synchronous PPO iteration (sample fleet -> n_epochs updates)."""
        key = jax.random.fold_in(self.seed_key, k)
        t0 = time.perf_counter()
        traj = self.orch.sample_fleet(self.params, key)
        traj = jax.block_until_ready(traj)
        t_sample = time.perf_counter() - t0
        if self.failure_injector is not None:
            self.failure_injector(k)  # may raise — exercised by tests
        t0 = time.perf_counter()
        new_params, new_opt, stats = self._update(
            self.params, self.opt_state, traj)
        stats = jax.device_get(stats)
        # never let a non-finite update poison the params / checkpoints:
        # keep the previous state and record the skip (env-level blow-up
        # guards make this a last line of defense, not the common path)
        if not all(jnp.isfinite(v).all() for v in stats.values()):
            self._log({"iteration": k, "skipped_nonfinite_update": True})
        else:
            self.params, self.opt_state = new_params, new_opt
        t_update = time.perf_counter() - t0
        record = {
            "iteration": k,
            "t_sample_s": t_sample,
            "t_update_s": t_update,
            # episode length read off the trajectory, not the env config —
            # envs with different horizons keep the metric correct
            "return_norm": float(stats["mean_return"]) / traj.rewards.shape[0],
            **{f"ppo/{n}": float(v) for n, v in stats.items()},
        }
        return record

    def train(self, n_iterations: int | None = None, *, resume: bool = True,
              max_retries: int = 2) -> list[dict]:
        """The full loop with crash recovery.  Returns per-iteration records."""
        total = n_iterations or self.run_cfg.n_iterations
        if resume:
            self.restore()
        history: list[dict] = []
        while self.iteration < total:
            k = self.iteration
            for attempt in range(max_retries + 1):
                try:
                    record = self.run_iteration(k)
                    break
                except RuntimeError as e:  # injected / transient failure
                    if attempt == max_retries:
                        raise
                    # deterministic replay: restore the consistent state and retry
                    if not self.restore():
                        pass  # no checkpoint yet: params/opt unchanged pre-update
                    record = {"iteration": k, "retry": attempt + 1, "error": str(e)}
                    self._log(record)
            if (k + 1) % self.run_cfg.eval_every == 0:
                record["eval_return_norm"] = float(self.orch.evaluate(self.params))
            self._log(record)
            history.append(record)
            self.iteration = k + 1
            if (k + 1) % self.run_cfg.checkpoint_every == 0:
                self.save_checkpoint()
        self.save_checkpoint(block=True)
        self.join_pending_checkpoint()
        return history
