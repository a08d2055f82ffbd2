"""Scheduler-gym benchmark: env throughput and trained-vs-untrained RLDS.

Two arms, written to ``BENCH_gym.json`` (CI runs ``--smoke``):

1. **Throughput** — env steps/sec swept over E (parallel environments) x K
   (pool size), in two execution modes:

   - ``stepwise`` (E=1) — one jitted dispatch per round: the execution
     model of the sequential Python loop the gym replaces (RLDS's old
     constructor pre-training drove the simulator exactly like this).
   - ``fused`` (every E) — the gym's lax.scan-over-rounds + vmap-over-envs
     rollout in a single dispatch.

   The headline number is fused@E=max vs stepwise@E=1 at fixed K: the
   vectorized gym must amortize per-step dispatch by >=10x or it cannot
   out-collect the loop it replaces. The fused E=1 -> E=max ratio is also
   recorded (on many-core/accelerator hosts it tracks the same claim; on
   a 2-core CI box fused E=1 is already compute-bound, so the stepwise
   baseline is the meaningful one).

2. **Policy quality** — a gym-trained RLDS policy vs the untrained
   (random-init, no-pretrain) policy on paired held-out scenarios
   (identical eval seed, deterministic top-k conversion). The run FAILS
   (exit 1) if trained mean cost exceeds untrained — the regression gate
   CI enforces per PR.

  PYTHONPATH=src python -m benchmarks.bench_gym            # full sweep
  PYTHONPATH=src python -m benchmarks.bench_gym --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

FULL_KS = [64, 256]
FULL_ES = [1, 4, 16, 64, 256]
SMOKE_KS = [64]
SMOKE_ES = [1, 16, 256]


def _time_loop(fn, min_s: float = 0.5, max_reps: int = 200) -> float:
    fn()  # warm-up (compile)
    reps, t0 = 0, time.perf_counter()
    while True:
        fn()
        reps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= min_s or reps >= max_reps:
            break
    return elapsed / reps


def bench_throughput(Ks, Es, rollout_len: int) -> list:
    """Environment steps/sec, same random-action workload in both modes.

    ``stepwise`` @ E=1 dispatches one jitted ``step`` per round (how the
    sequential pre-gym loop consumed the simulator); ``fused`` runs the
    whole (E, T) rollout in one scan+vmap dispatch. ``policy`` rows add
    the RLDS network in the loop (training throughput, fused only).
    """
    from repro.core.schedulers.rlds import init_policy
    from repro.gym import CURRICULA, EnvConfig, batch_reset, batch_rollout
    from repro.gym.env import (available_mask, batch_random_rollout,
                               plan_from_gumbel, release_instant, step)

    scen = CURRICULA["default"]
    params = init_policy(jax.random.PRNGKey(0))
    rows = []
    for K in Ks:
        cfg = EnvConfig(num_devices=K, num_jobs=3, n_sel=max(1, K // 10))

        # Sequential baseline: one jitted env dispatch per round, drawing a
        # random Gumbel top-k plan inside the call — the SAME per-step
        # workload the fused arm runs, minus only the scan/vmap fusion.
        state0 = batch_reset(cfg, scen, jax.random.PRNGKey(1), 1)
        state1 = jax.tree_util.tree_map(lambda x: x[0], state0)

        @jax.jit
        def stepped(s):
            key, k_plan = jax.random.split(s.key)
            s = s._replace(key=key)
            now = release_instant(cfg, s)
            plan = plan_from_gumbel(
                jnp.zeros(cfg.num_devices),
                jax.random.gumbel(k_plan, (cfg.num_devices,)),
                available_mask(s, now), cfg.n_sel)
            return step(cfg, s, plan)

        def run_stepwise():
            s = state1
            for _ in range(rollout_len):
                s, out = stepped(s)
            out.cost.block_until_ready()

        per_call = _time_loop(run_stepwise, max_reps=50)
        stepwise_sps = rollout_len / per_call
        rows.append({"K": K, "E": 1, "mode": "stepwise",
                     "rollout_len": rollout_len,
                     "env_steps_per_sec": stepwise_sps})
        print(f"  K={K:>4} E=   1 stepwise: {stepwise_sps:>10.0f} env steps/s"
              f" (sequential per-round dispatch baseline)")

        for mode, make_fn in (
                ("fused", lambda: jax.jit(
                    lambda s: batch_random_rollout(cfg, s, rollout_len))),
                ("policy", lambda: jax.jit(
                    lambda s: batch_rollout(cfg, params, s, rollout_len)))):
            for E in Es:
                roll = make_fn()
                states = batch_reset(cfg, scen, jax.random.PRNGKey(1), E)

                def run_fused():
                    _, out = roll(states)
                    out.cost.block_until_ready()

                per_call = _time_loop(run_fused, max_reps=50)
                sps = E * rollout_len / per_call
                r = {"K": K, "E": E, "mode": mode,
                     "rollout_len": rollout_len, "env_steps_per_sec": sps,
                     "scaling_vs_stepwise": sps / stepwise_sps}
                rows.append(r)
                print(f"  K={K:>4} E={E:>4} {mode:8s}: {sps:>10.0f} env "
                      f"steps/s (x{r['scaling_vs_stepwise']:.1f} vs "
                      "stepwise)")
    return rows


def bench_policy(smoke: bool) -> dict:
    from repro.core.schedulers.rlds import init_policy
    from repro.gym import TrainConfig, default_stages, evaluate, train_rlds

    tcfg = (TrainConfig(num_envs=16, rollout_len=16, iters=40)
            if smoke else TrainConfig(num_envs=32, rollout_len=32, iters=120))
    stages = default_stages("default", num_devices=(64,), num_jobs=3)
    print(f"  training: E={tcfg.num_envs} T={tcfg.rollout_len} "
          f"iters={tcfg.iters}")
    t0 = time.perf_counter()
    params, logs = train_rlds(stages, tcfg, seed=0)
    train_s = time.perf_counter() - t0

    cfg, scen = stages[0]
    untrained = init_policy(jax.random.PRNGKey(99))
    episodes, steps = (16, 32) if smoke else (32, 64)
    ev_t = evaluate(cfg, scen, params, seed=7, episodes=episodes, steps=steps)
    ev_u = evaluate(cfg, scen, untrained, seed=7, episodes=episodes,
                    steps=steps)
    improvement = ev_u["mean_cost"] / max(ev_t["mean_cost"], 1e-12)
    print(f"  trained mean_cost={ev_t['mean_cost']:.4f}  "
          f"untrained={ev_u['mean_cost']:.4f}  (x{improvement:.2f} better, "
          f"trained in {train_s:.1f}s)")
    return {
        "train_config": tcfg._asdict(), "train_wall_s": train_s,
        "train_log_head": logs[:3], "train_log_tail": logs[-3:],
        "trained_mean_cost": ev_t["mean_cost"],
        "untrained_mean_cost": ev_u["mean_cost"],
        "trained_mean_round_time": ev_t["mean_round_time"],
        "untrained_mean_round_time": ev_u["mean_round_time"],
        "improvement": improvement,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (fewer E/K points, short training)")
    ap.add_argument("--out", default="BENCH_gym.json")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="allowed trained/untrained cost slack "
                         "(0.0 = trained must be at least as good)")
    args = ap.parse_args(argv)

    Ks = SMOKE_KS if args.smoke else FULL_KS
    Es = SMOKE_ES if args.smoke else FULL_ES
    T = 16 if args.smoke else 32

    print(f"== gym throughput (E sweep {Es}, K sweep {Ks}) ==")
    throughput = bench_throughput(Ks, Es, T)

    # Per-K summary: E=1 (sequential per-round dispatch) -> E=max (fused
    # vmap), identical random-action env workload on both sides.
    scaling = {}
    for K in Ks:
        by = {(r["E"], r["mode"]): r["env_steps_per_sec"]
              for r in throughput if r["K"] == K}
        scaling[str(K)] = {
            "stepwise_E1": by[(1, "stepwise")],
            "fused_E1": by[(1, "fused")],
            "fused_Emax": by[(max(Es), "fused")],
            "policy_Emax": by[(max(Es), "policy")],
            "scaling_E1_to_Emax": by[(max(Es), "fused")] / by[(1, "stepwise")],
            "scaling_fused_E1_to_Emax": by[(max(Es), "fused")] / by[(1, "fused")],
        }
        print(f"  K={K}: E=1 -> E={max(Es)} env scaling "
              f"x{scaling[str(K)]['scaling_E1_to_Emax']:.1f} "
              f"(fused vmap vs per-step dispatch)")

    print("== trained vs untrained RLDS (paired held-out scenarios) ==")
    policy = bench_policy(args.smoke)

    out = {"smoke": args.smoke, "jax_backend": jax.default_backend(),
           "Ks": Ks, "Es": Es, "throughput": throughput,
           "scaling": scaling, "policy": policy}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out}")

    # Regression gate: a gym-trained policy must not be worse than the
    # untrained one it replaces.
    limit = policy["untrained_mean_cost"] * (1.0 + args.tol)
    if policy["trained_mean_cost"] > limit:
        print(f"REGRESSION: trained mean cost {policy['trained_mean_cost']:.4f} "
              f"> untrained {policy['untrained_mean_cost']:.4f} "
              f"(tol {args.tol})", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.bootstrap import setup_compile_cache

    setup_compile_cache()
    main()
