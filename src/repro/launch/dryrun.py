import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")

"""Multi-pod AOT dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init).  For every cell this script:

    1. builds ShapeDtypeStruct stand-ins for params / optimizer / inputs
       (no allocation — the 35B cells never materialize),
    2. jits the cell program with explicit in/out shardings on the
       production mesh and `.lower().compile()`s it,
    3. records memory_analysis() (proof it fits), cost_analysis() (FLOPs /
       bytes for §Roofline), and the per-device collective traffic parsed
       from the optimized HLO,
    4. writes one JSON artifact per cell under benchmarks/artifacts/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
    python -m repro.launch.dryrun --all --mesh single
    python -m repro.launch.dryrun --all --mesh multi
Skipped cells (long_500k on full-attention archs) emit SKIP artifacts with
the reason — they are rows of the roofline table, not silent omissions.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from .. import configs
from ..configs.shapes import SHAPES
from ..models import lm as lm_mod
from . import DRYRUN_ARTIFACT_DIR as ARTIFACT_DIR
from . import hlo_analysis, mesh as mesh_lib, specs


def _memory_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        keys = ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
        return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not implement it
        return {"error": str(e)}


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        return {k: float(v) for k, v in ca.items()
                if k in ("flops", "bytes accessed", "optimal_seconds",
                         "utilization operand")}
    except Exception as e:
        return {"error": str(e)}


def _calibration_cfgs(cfg):
    """(cfg_k1, cfg_k2, K): XLA's cost_analysis counts while-loop bodies
    ONCE, so a scanned layer stack under-reports FLOPs/bytes/collectives by
    ~the trip count.  We therefore lower the SAME cell at 1 and 2 layer
    groups with every sequence/layer scan python-unrolled and extrapolate

        total(K groups) = f(1) + (K - 1) * (f(2) - f(1)).

    Embedding/loss/prefix-layer work lands in the constant term; the
    per-group slope is exact.  (The linear-scan time chunking is capped at
    64 unrolled bodies — <~6% inflation on the tiny SSM/RWKV intra-chunk
    term, noted in EXPERIMENTS.md.)"""
    if cfg.is_encdec:
        # whisper: encoder and decoder stacks both scale with k (4 == 4)
        K = cfg.n_layers
        mk = lambda k: dataclasses.replace(cfg, n_layers=k, encoder_layers=k,
                                           scan_layers=False,
                                           unroll_scans=True)
        return mk(1), mk(2), K
    g = lm_mod.group_size(cfg)
    p = lm_mod.n_prefix(cfg)
    K = lm_mod.n_groups(cfg)
    # large groups (hymba g=8 -> 16 unrolled layers at k=2) need the inner
    # chunk unroll capped harder or the calibration compile takes tens of
    # minutes; ~+5% on the small SSM intra-chunk term (DESIGN.md §5b)
    chunk = max(cfg.scan_chunk, 1024) if g >= 4 else cfg.scan_chunk
    mk = lambda k: dataclasses.replace(cfg, n_layers=p + k * g,
                                       scan_layers=False, unroll_scans=True,
                                       scan_chunk=chunk)
    return mk(1), mk(2), K


def _lowered_costs(cfg, shape, mesh, rule_overrides,
                   opt_rule_overrides=None) -> dict:
    lowered, _ = specs.lower_cell(cfg, shape, mesh, rule_overrides,
                                  donate=False,
                                  opt_rule_overrides=opt_rule_overrides)
    # flop counts and collective shapes are fusion-independent: compile the
    # calibration programs at optimization level 0 (~1.7x faster)
    try:
        compiled = lowered.compile(
            compiler_options={"xla_backend_optimization_level": "0"})
    except Exception:
        compiled = lowered.compile()
    cost = _cost_analysis(compiled)
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": float(coll.total_bytes),
            "coll_by_kind": coll.bytes_by_kind}


def calibrated_costs(cfg, shape, mesh, rule_overrides,
                     opt_rule_overrides=None) -> dict:
    """Scan-corrected per-device flops / HBM bytes / collective bytes."""
    c1_cfg, c2_cfg, K = _calibration_cfgs(cfg)
    f1 = _lowered_costs(c1_cfg, shape, mesh, rule_overrides, opt_rule_overrides)
    f2 = _lowered_costs(c2_cfg, shape, mesh, rule_overrides, opt_rule_overrides)
    out = {}
    for key in ("flops", "bytes", "coll"):
        # clamp the per-group slope at 0: XLA occasionally CSEs collectives
        # harder in the 2-group program, which would extrapolate negative
        out[key] = f1[key] + (K - 1) * max(0.0, f2[key] - f1[key])
    out["coll_by_kind"] = {
        k: f1["coll_by_kind"][k]
        + (K - 1) * max(0, f2["coll_by_kind"][k] - f1["coll_by_kind"][k])
        for k in f1["coll_by_kind"]}
    out["calibration"] = {"K": K, "k1": f1, "k2": f2}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rule_overrides: dict | None = None, *, save: bool = True,
             tag: str = "", calibrate: bool = True,
             cfg_overrides: dict | None = None,
             opt_rule_overrides: dict | None = None) -> dict:
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    n_chips = 512 if multi_pod else 256

    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "kind": shape.kind, "status": "ok",
              "rules": rule_overrides or {}, "cfg": cfg_overrides or {}}

    for sh, runnable, reason in configs.cells(cfg):
        if sh.name == shape_name and not runnable:
            record.update(status="skip", reason=reason)
            _save(record, tag)
            return record
    if shape.kind == "decode" and cfg.family == "encoder-only":
        record.update(status="skip", reason="encoder-only: no decode step")
        _save(record, tag)
        return record

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    record["opt_rules"] = opt_rule_overrides or {}
    t0 = time.perf_counter()
    try:
        lowered, meta = specs.lower_cell(cfg, shape, mesh, rule_overrides,
                                         opt_rule_overrides=opt_rule_overrides)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = _memory_analysis(compiled)
        cost = _cost_analysis(compiled)
        hlo = compiled.as_text()
        coll = hlo_analysis.collective_bytes(hlo)
        dup = hlo_analysis.remat_duplication(hlo)

        if calibrate:
            cal = calibrated_costs(cfg, shape, mesh, rule_overrides,
                                   opt_rule_overrides)
            flops_dev, hbm_dev, coll_dev = cal["flops"], cal["bytes"], cal["coll"]
            coll_by_kind = cal["coll_by_kind"]
        else:  # raw (while bodies counted once — under-reports scans)
            cal = None
            flops_dev = cost.get("flops", 0.0)
            hbm_dev = cost.get("bytes accessed", 0.0)
            coll_dev = float(coll.total_bytes)
            coll_by_kind = coll.bytes_by_kind
        fused_bytes = None
        if all(k in mem for k in ("argument_size_in_bytes",
                                  "output_size_in_bytes",
                                  "temp_size_in_bytes")):
            fused_bytes = (mem["argument_size_in_bytes"]
                           + mem["output_size_in_bytes"]
                           + 2 * mem["temp_size_in_bytes"])
        terms = hlo_analysis.roofline_terms(
            flops_dev, hbm_dev, coll_dev, n_chips,
            mesh_lib.PEAK_FLOPS_BF16, mesh_lib.HBM_BW, mesh_lib.ICI_BW,
            fused_bytes_per_dev=fused_bytes)
        mf = hlo_analysis.model_flops(cfg, shape)
        record.update({
            "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "memory_analysis": mem,
            "cost_analysis_raw": cost,
            "flops_per_dev": flops_dev,
            "hbm_bytes_per_dev": hbm_dev,
            "collective_bytes_per_dev": coll_by_kind,
            "collective_counts_raw": coll.count_by_kind,
            "collective_total_per_dev": coll_dev,
            "calibration": cal["calibration"] if cal else None,
            "hlo_op_counts": dup,
            "roofline": terms,
            "model_flops_global": mf,
            "model_flops_per_dev": mf / n_chips,
            "useful_flop_ratio": (mf / n_chips) / flops_dev if flops_dev else None,
        })
    except Exception as e:
        record.update(status="fail", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    if save:
        _save(record, tag)
    return record


def _fleet_cell_costs(compiled, c1, c2, K: int, n_chips: int,
                      n_envs: int) -> dict:
    """Shared cost extraction for the RL fleet cells (HIT and channel):
    substep-scan calibration from the 1- and 2-substep compiles
    (cost_analysis counts while bodies once), memory/roofline terms, and
    the per-env step cost (`flops_per_env`) the fleet scheduler consumes
    as its sub-fleet weight (fleet/scheduler.dryrun_step_cost)."""
    def costs(comp):
        cost = _cost_analysis(comp)
        coll = hlo_analysis.collective_bytes(comp.as_text())
        return (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
                float(coll.total_bytes), coll.bytes_by_kind)

    f1, b1, l1, k1 = costs(c1)
    f2, b2, l2, k2 = costs(c2)
    flops = f1 + (K - 1) * (f2 - f1)
    hbm = b1 + (K - 1) * (b2 - b1)
    coll = l1 + (K - 1) * (l2 - l1)
    mem = _memory_analysis(compiled)
    fused = None
    if "temp_size_in_bytes" in mem:
        fused = (mem.get("argument_size_in_bytes", 0)
                 + mem.get("output_size_in_bytes", 0)
                 + 2 * mem["temp_size_in_bytes"])
    terms = hlo_analysis.roofline_terms(
        flops, hbm, coll, n_chips, mesh_lib.PEAK_FLOPS_BF16,
        mesh_lib.HBM_BW, mesh_lib.ICI_BW, fused_bytes_per_dev=fused)
    return {
        "n_substeps": K,
        "n_envs": n_envs,
        "memory_analysis": mem,
        "flops_per_dev": flops,
        "flops_per_env": flops * n_chips / n_envs,
        "hbm_bytes_per_dev": hbm,
        "collective_total_per_dev": coll,
        "collective_bytes_per_dev": {
            key: k1[key] + (K - 1) * (k2[key] - k1[key]) for key in k1},
        "roofline": terms,
    }


def run_relexi_cell(dof: int = 24, n_envs: int = 256, multi_pod: bool = False,
                    *, elem_axis: str | None = "model", tag: str = "",
                    save: bool = True) -> dict:
    """The paper's own cell: one synchronous MDP step of the HIT LES fleet
    (policy eval + Delta t_RL solver advance + reward) on the production
    mesh.  Environments shard over (pod, data) — the paper's weak-scaling
    axis; each environment's element grid shards over `model` — the paper's
    ranks-per-FLEXI strong-scaling axis (halo exchanges lower to
    collective-permute).  The substep scan is calibrated like the LM layer
    scans: lower at 1 and 2 substeps and extrapolate (cost_analysis counts
    while bodies once)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import relexi_hit
    from ..cfd import env as env_lib, spectra
    from ..core import policy as policy_lib
    from ..parallel import sharding as shd

    env_cfg = relexi_hit.HIT24 if dof == 24 else relexi_hit.HIT32
    if elem_axis:
        # pencil decomposition: the 16-way `model` axis splits into
        # (mx=4, my=4) so the 4x4x4-element grid shards 16 ways — the
        # paper's "16 MPI ranks per FLEXI" strong-scaling point
        shape = (2, 16, 4, 4) if multi_pod else (16, 4, 4)
        axes = (("pod", "data", "mx", "my") if multi_pod
                else ("data", "mx", "my"))
        mesh = mesh_lib.auto_mesh(shape, axes)
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    mesh_name = "multi" if multi_pod else "single"
    record = {"arch": f"relexi-hit{dof}", "shape": f"fleet_{n_envs}",
              "mesh": mesh_name, "kind": "rl_step", "status": "ok",
              "elem_axis": elem_axis}

    pcfg = policy_lib.PolicyConfig(n_nodes=env_cfg.n_poly + 1,
                                   cs_max=env_cfg.cs_max)
    n = env_cfg.n_poly + 1
    k = env_cfg.n_elem

    def lower_for(cfg_k):
        def mdp_k(params, u, e_dns):
            obs = env_lib.observe(u, cfg_k)
            action = policy_lib.actor_mean(params, pcfg, obs)
            state = env_lib.EnvState(u=u, t_step=jnp.zeros((n_envs,), jnp.int32))
            res = env_lib.step(state, action, cfg_k, e_dns)
            return res.state.u, res.reward

        # paper's two scaling axes: envs over (pod, data) [weak], elements
        # over model [strong].  Without element sharding the fleet claims
        # the model axis for environments too (1 env/chip at 256 envs).
        if elem_axis:
            env_axes = ("pod", "data") if multi_pod else ("data",)
            u_spec = P(env_axes, "mx", "my", None, None, None, None, None)
        else:
            env_axes = ("pod", "data", "model") if multi_pod else (
                "data", "model")
            u_spec = P(env_axes, None, None, None, None, None, None, None)
        with mesh:
            abstract_params = jax.eval_shape(
                lambda: policy_lib.init(jax.random.PRNGKey(0), pcfg))
            u_abs = jax.ShapeDtypeStruct(
                (n_envs, k, k, k, n, n, n, 5), jnp.float32)
            e_abs = jax.ShapeDtypeStruct(
                (len(spectra.reference_spectrum(cfg_k)),), jnp.float32)
            rep = NamedSharding(mesh, P())
            fn = jax.jit(mdp_k, in_shardings=(
                jax.tree.map(lambda _: rep, abstract_params),
                NamedSharding(mesh, u_spec), rep))
            return fn.lower(abstract_params, u_abs, e_abs).compile()

    try:
        t0 = time.perf_counter()
        compiled = lower_for(env_cfg)
        t_compile = time.perf_counter() - t0
        # calibration: 1 and 2 substeps (dt_rl = dt, 2*dt)
        c1 = lower_for(dataclasses.replace(env_cfg, dt_rl=env_cfg.dt * 1.0))
        c2 = lower_for(dataclasses.replace(env_cfg, dt_rl=env_cfg.dt * 2.0))
        record["t_compile_s"] = round(t_compile, 2)
        record.update(_fleet_cell_costs(compiled, c1, c2,
                                        env_cfg.n_substeps, n_chips, n_envs))
    except Exception as e:
        record.update(status="fail", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    if save:
        record["shape"] += f"_{'elem' + str(16) if elem_axis else 'noelem'}"
        _save(record, tag)
    return record


def run_channel_cell(n_envs: int = 256, multi_pod: bool = False, *,
                     variant: str = "channel_wm", tag: str = "",
                     save: bool = True) -> dict:
    """The channel-WMLES fleet cell: one synchronous MDP step (policy eval +
    Delta t_RL wall-modeled solver advance + profile reward) on the
    production mesh — `run_relexi_cell`'s sibling for the channel scenario,
    so its sharding can be sized the same way.

    The channel's element grid is anisotropic (Kx != Ky != Kz) and small
    (3x4x3 by default), so environments shard over ALL mesh axes
    ((pod, data, model)) rather than splitting element space; the substep
    scan is calibrated at 1 and 2 substeps exactly like the HIT cell.

    The artifact carries `flops_per_env` — the per-environment step cost
    the fleet scheduler consumes as its sub-fleet weight
    (`fleet/scheduler.dryrun_step_cost`).
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import envs as envs_mod
    from ..core import policy as policy_lib
    from ..envs.base import EnvState

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    mesh_name = "multi" if multi_pod else "single"
    record = {"arch": "channel-wm", "shape": f"fleet_{n_envs}",
              "mesh": mesh_name, "kind": "rl_step", "status": "ok",
              "variant": variant, "n_envs": n_envs}

    def lower_for(env):
        cfg = env.cfg
        pcfg = policy_lib.PolicyConfig.from_specs(env.obs_spec,
                                                  env.action_spec)

        def mdp(params, u):
            state = EnvState(u=u, t_step=jnp.zeros((n_envs,), jnp.int32))
            action = policy_lib.actor_mean(params, pcfg, env.observe(state))
            res = env.step(state, action)
            return res.state.u, res.reward

        env_axes = ("pod", "data", "model") if multi_pod else ("data",
                                                               "model")
        u_spec = P(env_axes, *([None] * 7))
        with mesh:
            abstract_params = jax.eval_shape(
                lambda: policy_lib.init(jax.random.PRNGKey(0), pcfg))
            kx, ky, kz = cfg.n_elem
            n = cfg.n
            u_abs = jax.ShapeDtypeStruct(
                (n_envs, kx, ky, kz, n, n, n, 5), jnp.float32)
            rep = NamedSharding(mesh, P())
            fn = jax.jit(mdp, in_shardings=(
                jax.tree.map(lambda _: rep, abstract_params),
                NamedSharding(mesh, u_spec)))
            return fn.lower(abstract_params, u_abs).compile()

    try:
        env = envs_mod.make(variant)
        cfg = env.cfg
        t0 = time.perf_counter()
        compiled = lower_for(env)
        t_compile = time.perf_counter() - t0
        # calibration: the same cell at 1 and 2 solver substeps
        c1 = lower_for(envs_mod.make(variant, dt_rl=cfg.dt * 1.0))
        c2 = lower_for(envs_mod.make(variant, dt_rl=cfg.dt * 2.0))
        record["t_compile_s"] = round(t_compile, 2)
        record.update(_fleet_cell_costs(compiled, c1, c2, cfg.n_substeps,
                                        n_chips, n_envs))
    except Exception as e:
        record.update(status="fail", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    if save:
        _save(record, tag)
    return record


def _save(record: dict, tag: str = "") -> None:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(
        ARTIFACT_DIR,
        f"{record['mesh']}_{record['arch']}_{record['shape']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--rules", default="",
                    help='JSON rule overrides, e.g. {"act_seq": null}')
    ap.add_argument("--opt-rules", default="",
                    help="JSON rule overrides for the Adam moments only "
                         "(ZeRO-1-style decoupled optimizer sharding)")
    ap.add_argument("--cfg", default="",
                    help='JSON ArchConfig overrides, e.g. '
                         '{"decode_combine": "flash"}')
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip scan calibration (pass/fail + memory only — "
                         "the multi-pod proof run)")
    ap.add_argument("--relexi", action="store_true",
                    help="run the paper's HIT fleet cell instead of LM cells")
    ap.add_argument("--channel", action="store_true",
                    help="run the channel-WMLES fleet cell (sizes the "
                         "channel sharding; feeds the fleet scheduler)")
    ap.add_argument("--variant", default="channel_wm",
                    help="registered channel scenario for --channel")
    ap.add_argument("--dof", type=int, default=24, choices=(24, 32))
    ap.add_argument("--n-envs", type=int, default=256)
    ap.add_argument("--no-elem-shard", action="store_true")
    args = ap.parse_args()

    if args.channel:
        for multi in {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]:
            rec = run_channel_cell(args.n_envs, multi, variant=args.variant,
                                   tag=args.tag)
            status = rec["status"]
            extra = (f"bound={rec['roofline']['bound']} "
                     f"frac={rec['roofline']['roofline_fraction']:.2f} "
                     f"flops/env={rec['flops_per_env']:.3g}"
                     if status == "ok" else rec.get("error", ""))
            print(f"[{rec['mesh']}] {rec['arch']:24s} {rec['shape']:12s} "
                  f"{status.upper():5s} {extra}", flush=True)
        return

    if args.relexi:
        for multi in {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]:
            rec = run_relexi_cell(
                args.dof, args.n_envs, multi,
                elem_axis=None if args.no_elem_shard else "model",
                tag=args.tag)
            status = rec["status"]
            extra = (f"bound={rec['roofline']['bound']} "
                     f"frac={rec['roofline']['roofline_fraction']:.2f}"
                     if status == "ok" else rec.get("error", ""))
            print(f"[{rec['mesh']}] {rec['arch']:24s} {rec['shape']:12s} "
                  f"{status.upper():5s} {extra}", flush=True)
        return

    overrides = json.loads(args.rules) if args.rules else None
    cfg_overrides = json.loads(args.cfg) if args.cfg else None
    opt_overrides = json.loads(args.opt_rules) if args.opt_rules else None
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    archs = configs.ARCH_NAMES if args.all or not args.arch else [args.arch]
    shapes = tuple(SHAPES) if args.all or not args.shape else [args.shape]

    n_ok = n_skip = n_fail = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, multi, overrides, tag=args.tag,
                               cfg_overrides=cfg_overrides,
                               calibrate=not args.no_calibrate,
                               opt_rule_overrides=opt_overrides)
                dt = time.perf_counter() - t0
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skip"
                n_fail += status == "fail"
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"bound={r['bound']} "
                             f"frac={r['roofline_fraction']:.2f} "
                             f"compile={rec['t_compile_s']}s")
                elif status == "skip":
                    extra = rec["reason"]
                else:
                    extra = rec["error"]
                print(f"[{'multi' if multi else 'single'}] {arch:24s} "
                      f"{shape:12s} {status.upper():5s} ({dt:5.1f}s) {extra}",
                      flush=True)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip, {n_fail} fail", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
