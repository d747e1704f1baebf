"""Serving cells: a trained controller answering solver clients through
`serve.load_service` (`ControllerService`: the bucket batcher and one
compiled `serve_step` per bucket).

Set-up makes the controller's weights from the seed, saves them as a fleet
checkpoint and loads the service from it, builds a pool of observations
from the configuration's initial states, and warms up every bucket of the
ladder.  The window offers open-loop arrivals at the traffic file's fixed
rate: `rate_per_s * seconds` requests, due at the sorted uniform times of
a Poisson process with that count, each carrying a pool observation drawn
from the seed.  One thread submits every request that is due and flushes
whenever requests are pending.  A request's latency runs from the moment
it was due to the return of the `flush()` that answered it; requests due
in the window are served to the end, after the window if need be.

Once the window has closed every answer is compared with the plain
reference on its observation.

Traffic keys: rate_per_s, pool_size.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import Check, Outcome
from bench.reference import ppo as ref_ppo
from bench.reference.hit_les import HITReference


def arrivals(rng: np.random.Generator, rate: float, seconds: float
             ) -> np.ndarray:
    """Due times (s after the window opens) of a Poisson process with
    exactly rate * seconds arrivals: sorted uniform times."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def make_pool(ref: HITReference, config: dict, size: int) -> np.ndarray:
    """Observations (size, E, n, n, n, 3) of the configuration's states."""
    a = config["assumed"]
    bank = ref.bank(jax.random.PRNGKey(a["bank_seed"]), size)
    return np.asarray(ref.observe(ref.to_planar(bank)), np.float32)


def make_service(config: dict, params, workdir: str):
    """Save `params` as a fleet checkpoint and load the service from it."""
    from repro import serve
    from repro.core import checkpoints

    a = config["assumed"]
    name = config["registry"]
    checkpoints.save(workdir, 0, {"params": params}, meta={
        "scenarios": [name], "d_embed": a["d_embed"],
        "n_shared_layers": a["n_shared_layers"], "iteration": 0})
    return serve.load_service(workdir, env_overrides={
        name: config["physics"]})


def warm_up(svc, name: str, pool: np.ndarray) -> None:
    """Every shape a flush can use: each bucket, and each count of valid
    rows sliced from it."""
    for count in range(1, svc.batcher.buckets[-1] + 1):
        svc.serve_batch(name, pool[np.arange(count) % len(pool)])


def offer(svc, name: str, pool: np.ndarray, due: np.ndarray,
          picks: np.ndarray, spans, seconds: float) -> dict:
    """Run the open loop; returns per-request due, submit and done times
    (host clock), the answers, and the refused requests."""
    n = len(due)
    submit_t = np.full(n, np.nan)
    done_t = np.full(n, np.nan)
    uid_of: dict[int, int] = {}
    answers: dict[int, tuple[np.ndarray, float]] = {}
    refused = 0
    t0 = time.perf_counter()
    due_abs = t0 + due
    i = 0
    with spans("bench.window"):
        while True:
            now = time.perf_counter()
            if i < n and due_abs[i] <= now:
                with spans("serve.submit"):
                    while i < n and due_abs[i] <= now:
                        try:
                            uid_of[svc.submit(name, pool[picks[i]])] = i
                            submit_t[i] = time.perf_counter()
                        except RuntimeError:   # slot pool full: refused
                            refused += 1
                        i += 1
            elif svc.batcher.n_pending:
                with spans("serve.flush"):
                    out = svc.flush()
                t = time.perf_counter()
                for uid, res in out.items():
                    j = uid_of.pop(uid)
                    done_t[j] = t
                    answers[j] = (res.action, res.value)
            elif i >= n:
                break
            else:
                with spans("generator.wait"):
                    wait = due_abs[i] - time.perf_counter()
                    if wait > 2e-3:
                        time.sleep(wait - 1e-3)
                    while time.perf_counter() < due_abs[i]:
                        pass
    t_end = t0 + seconds
    backlog_end = int(np.sum((due_abs <= t_end)
                             & ~(done_t <= t_end)))
    return {"t0": t0, "due": due_abs, "submit": submit_t, "done": done_t,
            "answers": answers, "refused": refused,
            "backlog_end": backlog_end}


def reference_answers(config: dict, params, pool: np.ndarray):
    """Greedy actions and values of the plain reference on every pool
    observation (float32, highest matmul precision)."""
    name = config["registry"]
    cs_max = config["physics"]["cs_max"]
    feats = ref_ppo.features(jnp.asarray(pool))
    with jax.default_matmul_precision("highest"):
        act = jax.jit(ref_ppo.actor_mean, static_argnums=(1, 3))(
            params, name, feats, cs_max)
        val = jax.jit(ref_ppo.value, static_argnums=(1,))(params, name,
                                                          feats)
    return np.asarray(act, np.float64), np.asarray(val, np.float64)


def answer_gaps(answers: dict, picks: np.ndarray, act_ref, val_ref,
                cs_max: float) -> dict[str, float]:
    """Widest gap of a served action (over the action range) and of a
    served value (over the largest reference value) to the reference."""
    act_gap, val_gap = 0.0, 0.0
    v_scale = max(float(np.max(np.abs(val_ref))), 1e-30)
    for j, (a, v) in answers.items():
        p = picks[j]
        da = np.max(np.abs(np.asarray(a, np.float64) - act_ref[p]))
        dv = abs(float(v) - val_ref[p])
        act_gap = max(act_gap, float(da) / cs_max if np.isfinite(da)
                      else math.inf)
        val_gap = max(val_gap, dv / v_scale if np.isfinite(dv)
                      else math.inf)
    return {"action_gap": act_gap, "value_gap": val_gap}


def setup(h):
    config, traffic = h.config, h.traffic
    ref = HITReference(config["physics"])
    a = config["assumed"]
    params = jax.jit(ref_ppo.init_params, static_argnums=(1, 2, 3, 4))(
        h.seed_key, config["registry"], 3 * ref.n**3, a["d_embed"],
        a["n_shared_layers"])
    pool = make_pool(ref, config, traffic["pool_size"])
    svc = make_service(config, params, h.tmp)
    warm_up(svc, config["registry"], pool)
    return ref, params, pool, svc


def run(h) -> Outcome:
    config, traffic = h.config, h.traffic
    name = config["registry"]
    ref, params, pool, svc = setup(h)
    rng = np.random.default_rng(h.seed)
    due = arrivals(rng, traffic["rate_per_s"], h.seconds)
    picks = rng.integers(0, len(pool), len(due))

    before = svc.stats()[name]
    compiles0 = h.clock.count
    with h.profile():
        res = offer(svc, name, pool, due, picks, h.spans, h.seconds)
    compiles = h.clock.count - compiles0
    after = svc.stats()[name]
    memory_peak = h.memory_peak()

    served = ~np.isnan(res["done"])
    lat_ms = (res["done"][served] - res["due"][served]) * 1e3
    lag_ms = (res["submit"][served] - res["due"][served]) * 1e3
    act_ref, val_ref = reference_answers(config, params, pool)
    gaps = answer_gaps(res["answers"], picks, act_ref, val_ref,
                       config["physics"]["cs_max"])
    checks = [Check(k, v, h.limits[k]) for k, v in gaps.items()]
    checks.append(Check("compiles_in_window", float(compiles), 0.0))
    batches = after["batches"] - before["batches"]
    return Outcome(
        attempted=len(due), failed=int(len(due) - served.sum()),
        e2e={"serve_p50_ms": float(np.percentile(lat_ms, 50)),
             "serve_p95_ms": float(np.percentile(lat_ms, 95))},
        checks=checks, memory_peak_bytes=memory_peak,
        context={"requests": after["requests"] - before["requests"],
                 "batches": batches,
                 "generator_lag_ms": lag_ms,
                 "backlog_end": res["backlog_end"]},
        notes={"served": int(served.sum()), "refused": res["refused"],
               "backlog_end": res["backlog_end"]})
