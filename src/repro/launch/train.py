"""Training drivers behind one launch entry point.

Provider-side LM training: runs a real (reduced or full) architecture with
the synthetic data pipeline on whatever devices exist.  On the CPU
container use ``--reduced`` (the full configs are exercised via
launch.dryrun instead).

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 50 --batch 8 --seq 128

Federation-side selector training: ``--federation`` trains the Armol
provider-selection agent through the multi-lane batched drivers
(``--lanes`` parallel env lanes, fused lax.scan update blocks; ``--lanes
1`` is bit-identical to the sequential reference).

  PYTHONPATH=src python -m repro.launch.train --federation --algo sac \
      --epochs 5 --steps 500 --images 400 --lanes 8

``--scenario`` switches to ONLINE adaptation on a non-stationary provider
pool (``repro.scenarios``): the schedule re-prices, degrades, downs, and
launches providers mid-stream while training continues, reporting per-
segment recovery vs the per-segment oracle.

  PYTHONPATH=src python -m repro.launch.train --federation \
      --scenario provider_outage --horizon 1600 --images 120
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.store import save_pytree
from repro.configs.base import get_arch
from repro.data.pipeline import synthetic_lm_batches
from repro.models.model import build_model
from repro.training.train_step import init_train_state, make_train_step


def run_scenario(args) -> int:
    """Online adaptation through a non-stationary provider scenario."""
    from repro.core.sac import SAC, SACConfig
    from repro.core.td3 import TD3, TD3Config
    from repro.federation.providers import default_providers
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario, run_online)

    if args.algo == "ppo":
        raise SystemExit("--scenario runs the off-policy online driver; "
                         "use --algo sac or td3")
    providers = default_providers()
    schedule = build_scenario(args.scenario, providers,
                              horizon=args.horizon, seed=args.seed)
    print(schedule.describe())
    pool = DynamicProviderPool(providers, schedule, n_images=args.images,
                               seed=args.seed)
    env = NonStationaryArmolEnv(pool, mode=args.mode, beta=args.beta,
                                observe_pool=not args.blind,
                                seed=args.seed + 1)
    kw = dict(state_dim=env.state_dim, n_providers=env.n_providers,
              lr=3e-4, gamma=0.0, hidden=(32, 32), seed=args.seed)
    agent = TD3(TD3Config(**kw)) if args.algo == "td3" \
        else SAC(SACConfig(alpha=0.02, **kw))
    obs = _make_obs(args)
    res = run_online(agent, env, lanes=args.lanes, seed=args.seed,
                     obs=obs)
    s = res["summary"]
    print(f"[train] scenario done: min post-switch recovery="
          f"{s['min_recovery_post_switch']} mean="
          f"{s['mean_recovery_post_switch']} "
          f"cache_hit={s['mean_cache_hit_rate']} ({s['steps']} steps, "
          f"{s['wall_s']}s)")
    _finish_obs(obs, args)
    return 0


def _make_obs(args):
    """Build the run's ``repro.obs.Obs`` handle from ``--obs-dir`` (or
    ``None`` — every driver treats that as observability off)."""
    if not getattr(args, "obs_dir", ""):
        return None
    from repro.obs import Obs
    return Obs(args.obs_dir, seed=args.seed)


def _finish_obs(obs, args) -> None:
    if obs is None:
        return
    obs.write_metrics()
    obs.close()
    print(f"[train] observability artifacts in {args.obs_dir} "
          f"(render: python -m repro.launch.obs_report {args.obs_dir})")


def run_federation(args) -> int:
    from repro.core.loops import run_off_policy, run_ppo
    from repro.core.ppo import PPO, PPOConfig
    from repro.core.sac import SAC, SACConfig
    from repro.core.td3 import TD3, TD3Config
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces

    if args.scenario:
        return run_scenario(args)
    traces = generate_traces(default_providers(), args.images,
                             seed=args.seed)
    env = ArmolEnv(traces, mode=args.mode, beta=args.beta,
                   seed=args.seed + 1)
    print(f"[train] federation selector: {env.n_providers} providers, "
          f"{args.images} images, algo={args.algo}, lanes={args.lanes}")
    t0 = time.time()
    if args.algo == "ppo":
        agent = PPO(PPOConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers,
                              seed=args.seed))
        hist = run_ppo(agent, env, lanes=args.lanes, epochs=args.epochs,
                       steps_per_epoch=args.steps)
        total = args.epochs * (-(-args.steps // args.lanes)) * args.lanes
    else:
        cls, cfg_cls = (TD3, TD3Config) if args.algo == "td3" \
            else (SAC, SACConfig)
        agent = cls(cfg_cls(state_dim=env.state_dim,
                            n_providers=env.n_providers, seed=args.seed))
        obs = _make_obs(args)
        hist = run_off_policy(agent, env, lanes=args.lanes,
                              epochs=args.epochs,
                              steps_per_epoch=args.steps, seed=args.seed,
                              obs=obs)
        total = hist[-1]["steps"]
        _finish_obs(obs, args)
    dt = time.time() - t0
    last = hist[-1]
    print(f"[train] done: AP50={last['ap50']:.2f} cost={last['cost']:.3f} "
          f"({total / max(dt, 1e-9):.0f} env steps/s over {total} steps)")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="",
                    help="LM architecture (required unless --federation)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=None,
                    help="LM: training steps (default 50); federation: "
                         "env steps per epoch (default 500)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--federation", action="store_true",
                    help="train the Armol provider-selection agent on the "
                         "batched multi-lane drivers")
    ap.add_argument("--algo", choices=["sac", "td3", "ppo"], default="sac")
    ap.add_argument("--mode", choices=["gt", "nogt"], default="gt")
    ap.add_argument("--beta", type=float, default=-0.03)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--images", type=int, default=400)
    ap.add_argument("--scenario", default="",
                    help="federation: train ONLINE through a non-"
                         "stationary provider scenario (price_war, "
                         "provider_outage, accuracy_drift, flash_crowd, "
                         "provider_churn, random[:seed])")
    ap.add_argument("--horizon", type=int, default=1600,
                    help="scenario: schedule length in env steps")
    ap.add_argument("--blind", action="store_true",
                    help="scenario: hide provider status/fees from the "
                         "state (adaptation from reward alone)")
    ap.add_argument("--obs-dir", default="",
                    help="write observability artifacts (metrics.json, "
                         "events.jsonl) to this directory; training "
                         "results are bit-identical with or without it")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.federation:
        # the shared --steps flag means env steps per epoch here; the LM
        # default of 50 would end training before the first update block
        if args.steps is None:
            args.steps = 500
        return run_federation(args)
    if args.steps is None:
        args.steps = 50
    if not args.arch:
        ap.error("--arch is required unless --federation is given")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, dtype=jnp.float32 if args.reduced else None)
    state = init_train_state(model, jax.random.PRNGKey(args.seed))
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    print(f"[train] {cfg.name} ({'reduced' if args.reduced else 'full'}): "
          f"{n_params / 1e6:.1f}M params, {len(jax.devices())} device(s)")

    step_fn = jax.jit(make_train_step(model, peak_lr=args.lr,
                                      total_steps=args.steps))
    data = synthetic_lm_batches(cfg, args.batch, args.seq, seed=args.seed)
    obs = _make_obs(args)
    h_step = obs.metrics.histogram("train.lm_step_ms") \
        if obs is not None else None
    t0 = time.time()
    for step in range(args.steps):
        st0 = time.monotonic() if h_step is not None else 0.0
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        state, metrics = step_fn(state, batch)
        if h_step is not None:
            h_step.observe((time.monotonic() - st0) * 1e3)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({(time.time() - t0) / (step + 1):.2f}s/step)")
    if args.ckpt:
        save_pytree(args.ckpt, state.params)
        print(f"[train] saved params to {args.ckpt}")
    _finish_obs(obs, args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
