"""Bring-up check of the main path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the data-parallel fleet over four chips

One chip, at paper Table 1 width (`hit_les_24dof`: N=5, 4^3 elements,
13,824 DOF, 50 RL steps per episode) with random weights from a seed:

  1. kernel check  the compiled fused RHS (16 meshes) and `dg_derivative3`
                   against their XLA-compiled references in kernels/ref.py,
                   within the float32 tolerances of
                   tests/test_kernel_parity.py;
  2. train         `repro.launch.rl_train` (Runner, mesh on): 16 envs, 2 PPO
                   iterations, then 1 more at steady state;
  3. fleet+serve   `FleetRunner` (one compiled program per iteration) on
                   `hit_les_24dof` + `channel_wm` at their registered sizes
                   (16 envs in all): 2 iterations, then 1 at steady state,
                   with a checkpoint; `serve.load_service` on it answers 8
                   requests per scenario, which must equal
                   `multitask.actor_mean` bit for bit.

`--chips 4` runs only the multi-chip path and what it is compared with: one
`FleetProgram` iteration of `hit_les_24dof` with 64 envs over a `data=4`
mesh and over a one-device mesh, in this process.  Every device must hold
16 envs, and the per-env returns and the updated params must agree.

Every phase runs in this process (a chip belongs to one process) on a fresh
checkpoint directory, and fails on any retry or skipped update, a
non-finite return, or fewer iterations than asked.  Each phase prints one
JSON line (compile and steady seconds, kernel implementations, checks);
the last line is `{"ok": true, "device": {...}}`.  Without a TPU the script
exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# float32 tolerances of tests/test_kernel_parity.py
RHS_TOL = dict(rtol=2e-4, atol=2e-4)
DG_TOL = dict(rtol=2e-4, atol=1e-5)
# One device vs four.  Per-env returns: the same per-env programs, but XLA
# may fuse a batch of 64 and a shard of 16 differently.  Params: the PPO
# update's batch sums are all-reduced in another order, and Adam's first
# steps move each coordinate by ~lr * sign(g), so a coordinate whose
# gradient is at rounding level may move either way: the two updates are
# compared as vectors, |p4 - p1| <= PARAMS_REL_TOL * |p1 - p0| (p0 the
# params both started from).  A wrong gradient or a different data split
# gives a ratio of order 1.
MESH_TOL = dict(rtol=1e-4, atol=1e-5)
PARAMS_REL_TOL = 0.05

HIT = "hit_les_24dof"
FLEET = ("hit_les_24dof", "channel_wm")


class CompileClock:
    """Seconds the XLA backend spends compiling (JAX's
    `backend_compile_duration` events; tracing and lowering, which nest
    across jit levels, are left out)."""

    def __init__(self):
        self.secs = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


def kernel_impls(env) -> dict[str, str]:
    """Solver kernels an env's RHS runs, and how."""
    from repro.cfd.solver import HITConfig
    from repro.kernels.policy import default_interpret

    names = (("fused_ns_rhs",) if isinstance(env.cfg, HITConfig) else
             ("dg_derivative3", "smagorinsky_nut", "wall_model_tau"))
    how = ("jnp" if not env.cfg.kernels_enabled else
           "pallas-interpret" if default_interpret() else "pallas-compiled")
    return {n: how for n in names}


def _timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _max_err(got, want, rtol: float, atol: float) -> float:
    """Largest |got - want| / (atol + rtol |want|): <= 1 within tolerance."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        return float("inf")
    return float(np.max(np.abs(got - want) / (atol + rtol * np.abs(want))))


def phase_kernel_check() -> dict:
    import jax
    import jax.numpy as jnp

    from repro import envs
    from repro.kernels import ref
    from repro.kernels.dg_derivative import dg_derivative3
    from repro.kernels.rhs import fused_navier_stokes_rhs

    env = envs.make(HIT)
    cfg = env.cfg
    ops = cfg.operators()
    kw = dict(inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
              delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
              prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
              k_tke=cfg.k_tke)
    u = env.initial_state_bank(jax.random.PRNGKey(0), 16)
    cs = jnp.full(u.shape[:-1], 0.17, u.dtype)
    kernel = jax.jit(lambda u, cs: fused_navier_stokes_rhs(
        u, cs, ops["D"], ops["w"], **kw))
    oracle = jax.jit(lambda u, cs: ref.navier_stokes_rhs_fused(
        u, cs, ops["D"], ops["w"], **kw))
    got, _ = _timed(kernel, u, cs)
    _, steady = _timed(kernel, u, cs)
    want = oracle(u, cs)
    rhs_err = _max_err(got, want, **RHS_TOL)

    n = cfg.n_poly + 1
    x = jax.random.normal(jax.random.PRNGKey(5), (16 * 64, n, n, n, 4))
    d = jax.random.normal(jax.random.PRNGKey(6), (n, n))
    dg_got = jax.jit(dg_derivative3)(x, d)
    with jax.default_matmul_precision("float32"):
        dg_want = jax.jit(ref.dg_derivative3)(x, d)
    dg_err = max(_max_err(g, w, **DG_TOL) for g, w in zip(dg_got, dg_want))
    if not (rhs_err <= 1.0 and dg_err <= 1.0):
        raise AssertionError(
            f"kernel check failed: fused RHS error {rhs_err} and "
            f"dg_derivative3 error {dg_err} (in units of the tolerance)")
    return {"steady_s": steady, "kernels": kernel_impls(env),
            "rhs_err_over_tol": rhs_err, "dg_err_over_tol": dg_err,
            "rhs_shape": list(u.shape)}


def phase_train(ckpt_dir: str) -> dict:
    from repro import envs
    from repro.launch import rl_train

    history = rl_train.main([
        "--env", HIT, "--n-envs", "16", "--iterations", "3",
        "--checkpoint-dir", ckpt_dir])
    if len(history) != 3:
        raise AssertionError(f"{len(history)} of 3 iterations ran")
    steady = history[-1]["t_sample_s"] + history[-1]["t_update_s"]
    return {"steady_s": steady, "kernels": kernel_impls(envs.make(HIT)),
            "n_envs": 16, "iterations": len(history),
            "return_norm": [r["return_norm"] for r in history]}


def phase_fleet_serve(ckpt_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import envs, serve
    from repro.core.runner import read_metrics, run_faults
    from repro.envs.base import EnvState
    from repro.fleet import make_fleet_runner, multitask
    from repro.fleet.pipeline import FleetRunnerConfig
    from repro.launch import mesh as mesh_lib

    runner = make_fleet_runner(
        FLEET, total_envs=16, mesh=mesh_lib.make_fleet_mesh(),
        run_cfg=FleetRunnerConfig(n_iterations=2, eval_every=1000,
                                  checkpoint_every=1000,
                                  async_checkpoint=False,
                                  checkpoint_dir=ckpt_dir),
        use_artifacts=False)
    history = runner.train(resume=False)
    t0 = time.perf_counter()
    history += runner.train(n_iterations=3, resume=False)
    steady = time.perf_counter() - t0
    keys = tuple(f"{n}/return_norm" for n in FLEET)
    faults = run_faults(read_metrics(runner.metrics_path), 3, keys)
    if faults:
        raise AssertionError("fleet run not clean: " + "; ".join(faults))

    svc = serve.load_service(ckpt_dir)
    reference = jax.jit(multitask.actor_mean, static_argnums=(1, 2))
    served = {}
    for name in FLEET:
        orch = runner.forch.orchs[name]
        state = EnvState(u=orch.bank[:8], t_step=jnp.zeros((8,), jnp.int32))
        obs = np.asarray(orch.env.observe(state))
        got = svc.serve_batch(name, obs)
        want = np.asarray(reference(runner.params, runner.mcfg, name,
                                    jnp.asarray(obs)))
        if not (np.all(np.isfinite(got)) and np.array_equal(got, want)):
            raise AssertionError(f"{name}: served actions differ from "
                                 "multitask.actor_mean")
        served[name] = {"requests": len(obs), "bit_identical": True}
    return {"steady_s": steady,
            "kernels": {n: kernel_impls(envs.make(n)) for n in FLEET},
            "n_envs": {m.name: m.n_envs for m in runner.schedule.members},
            "iterations": len(history),
            "return_norm": {k: [r[k] for r in history] for k in keys},
            "served": served}


def phase_four_chips(ckpt_root: str) -> dict:
    import jax
    import numpy as np

    from repro import envs
    from repro.core.runner import read_metrics, run_faults
    from repro.fleet import make_fleet_runner
    from repro.fleet.pipeline import FleetRunnerConfig
    from repro.launch import mesh as mesh_lib

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    meshes = {"data4": mesh_lib.make_fleet_mesh(),
              "one_device": mesh_lib.auto_mesh((1, 1), ("data", "model"),
                                               devices=devices[:1])}
    out, returns, params = {}, {}, {}
    p0 = None
    for label, mesh in meshes.items():
        runner = make_fleet_runner(
            (HIT,), total_envs=64, mesh=mesh,
            run_cfg=FleetRunnerConfig(
                n_iterations=1, eval_every=1000, checkpoint_every=1000,
                async_checkpoint=False,
                checkpoint_dir=str(Path(ckpt_root) / label)),
            use_artifacts=False)
        p0 = jax.tree.map(np.asarray, runner.params)
        t0 = time.perf_counter()
        runner.train(resume=False)
        wall = time.perf_counter() - t0
        faults = run_faults(read_metrics(runner.metrics_path), 1,
                            (f"{HIT}/return_norm",))
        if faults:
            raise AssertionError(f"{label}: " + "; ".join(faults))
        # the broker ring holds the prologue's and iteration 0's rollouts,
        # (slot, T, env), laid out as the fleet program left them
        rewards = runner.broker.traj[HIT].data.rewards
        per_device = sorted({s.data.shape[2]
                             for s in rewards.addressable_shards})
        returns[label] = np.asarray(rewards).sum(axis=1)
        params[label] = jax.tree.map(np.asarray, runner.params)
        out[label] = {"devices": int(mesh.devices.size),
                      "envs_per_device": per_device,
                      "train_s_incl_compile": wall}
    if out["data4"]["envs_per_device"] != [16]:
        raise AssertionError(f"data=4 mesh placed "
                             f"{out['data4']['envs_per_device']} envs per "
                             "device, expected 16")
    ret_err = _max_err(returns["data4"], returns["one_device"], **MESH_TOL)
    flat = {k: np.concatenate([x.ravel() for x in jax.tree.leaves(v)])
            for k, v in {**params, "start": p0}.items()}
    diff = flat["data4"] - flat["one_device"]
    par_rel = float(np.linalg.norm(diff)
                    / np.linalg.norm(flat["one_device"] - flat["start"]))
    if not (ret_err <= 1.0 and par_rel <= PARAMS_REL_TOL):
        raise AssertionError(
            f"data=4 vs one device: per-env return error {ret_err} (in "
            f"units of {MESH_TOL}), params |p4 - p1| / |p1 - p0| = {par_rel}"
            f" (limit {PARAMS_REL_TOL})")
    return {"kernels": kernel_impls(envs.make(HIT)), "n_envs": 64,
            "meshes": out, "return_err_over_tol": ret_err,
            "return_tolerance": MESH_TOL,
            "params_rel_diff": par_rel, "params_rel_limit": PARAMS_REL_TOL,
            "params_max_abs_diff": float(np.max(np.abs(diff))),
            "params_outside_return_tol": int(np.sum(
                np.abs(diff) > MESH_TOL["atol"] + MESH_TOL["rtol"]
                * np.abs(flat["one_device"]))),
            "n_params": int(diff.size)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU here (jax.devices()[0].platform = "
              f"{device.platform!r})", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "jax": jax.__version__}), flush=True)

    ckpt_root = ROOT / "checkpoints"
    ckpt_root.mkdir(exist_ok=True)
    if args.chips == 4:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("kernel_check", lambda _: phase_kernel_check()),
                  ("train", phase_train),
                  ("fleet_serve", phase_fleet_serve)]
    for name, fn in phases:
        with tempfile.TemporaryDirectory(dir=ckpt_root,
                                         prefix=f"chip_smoke_{name}_") as d:
            compile0, t0 = clock.secs, time.perf_counter()
            info = fn(d)
            print(json.dumps({"phase": name, "ok": True,
                              "wall_s": time.perf_counter() - t0,
                              "compile_s": clock.secs - compile0, **info}),
                  flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
