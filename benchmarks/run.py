"""Benchmark harness — one function per paper table/figure.

  Table I   -> bench_provider_ap          (per-provider mAP / AP50 / AP75)
  Fig. 2    -> bench_ensemble_combos      (AP50 of provider combinations)
  Table II  -> bench_baselines            (Random-1/N, Ensemble-N, Armol
                                           w/gt, w/o gt, PPO, TD3, UB)
  Fig. 6/7  -> bench_baselines also emits per-epoch AP50/cost curves
  Table III -> bench_scalability          (10 providers, 1023 actions)
  kernels   -> bench_kernels              (us_per_call vs jnp reference)

Budgets are sized for the CPU container; set REPRO_BENCH_EPOCHS /
REPRO_BENCH_IMAGES / REPRO_BENCH_STEPS to scale up (paper scale: 100
epochs x 2000 steps, batch 1000).  Results land in benchmarks/results/
*.json and are printed as ``name,us_per_call,derived`` CSV lines.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import time

import numpy as np

# REPRO_RESULTS_DIR lets tools/check_bench.py collect fresh numbers in a
# scratch dir without clobbering the committed baselines
RESULTS_DIR = os.environ.get(
    "REPRO_RESULTS_DIR",
    os.path.join(os.path.dirname(__file__), "results"))

EPOCHS = int(os.environ.get("REPRO_BENCH_EPOCHS", "3"))
IMAGES = int(os.environ.get("REPRO_BENCH_IMAGES", "400"))
STEPS = int(os.environ.get("REPRO_BENCH_STEPS", "400"))


def _emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _save(name: str, obj) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=str)


def _traces():
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces
    return generate_traces(default_providers(), IMAGES, seed=0)


def _best_of(*fns, rounds: int = 3, warmup: bool = True):
    """Best-of-``rounds`` wall seconds for each candidate in ``fns``.

    The candidates' timed passes interleave round-by-round (fn0, fn1,
    ..., fn0, fn1, ...), so a load spike on a shared machine hits every
    candidate instead of biasing whichever ran during the spike; each
    keeps its best round.  ``warmup`` runs one untimed pass of each
    first, absorbing jit/compile/memo cost — turn it off for cold-path
    benchmarks whose setup cost IS the measurement.  Returns a float for
    a single candidate, else a list in ``fns`` order.
    """
    if warmup:
        for fn in fns:
            fn()
    best = [float("inf")] * len(fns)
    for _ in range(rounds):
        for k, fn in enumerate(fns):
            t0 = time.time()
            fn()
            best[k] = min(best[k], time.time() - t0)
    return best[0] if len(fns) == 1 else best


# ---------------------------------------------------------------------------
# Table I: per-provider AP
# ---------------------------------------------------------------------------

def bench_provider_ap(traces=None):
    from repro.ensemble.metrics import average_precision, ap50, coco_map
    traces = traces if traces is not None else _traces()
    gts = {i: g for i, g in enumerate(traces.gts)}
    rows = {}
    t0 = time.time()
    for pi, p in enumerate(traces.providers):
        dts = {i: traces.dets[i][pi] for i in range(len(traces))}
        rows[p.name] = {
            "mAP": round(100 * coco_map(dts, gts), 2),
            "AP50": round(100 * ap50(dts, gts), 2),
            "AP75": round(100 * average_precision(dts, gts, iou_thr=0.75),
                          2)}
    us = (time.time() - t0) * 1e6 / max(len(traces) * 3, 1)
    _save("table1_provider_ap", rows)
    for name, r in rows.items():
        _emit(f"table1/{name}", us,
              f"mAP={r['mAP']};AP50={r['AP50']};AP75={r['AP75']}")
    return rows


# ---------------------------------------------------------------------------
# Fig. 2: ensemble combinations
# ---------------------------------------------------------------------------

def bench_ensemble_combos(traces=None):
    from repro.ensemble.metrics import ap50
    from repro.ensemble.pipeline import ensemble_detections
    traces = traces if traces is not None else _traces()
    gts = {i: g for i, g in enumerate(traces.gts)}
    names = [p.name for p in traces.providers]
    rows = {}
    t0 = time.time()
    for r in range(1, len(names) + 1):
        for combo in itertools.combinations(range(len(names)), r):
            dts = {i: ensemble_detections([traces.dets[i][c] for c in combo])
                   for i in range(len(traces))}
            rows["+".join(names[c] for c in combo)] = round(
                100 * ap50(dts, gts), 2)
    us = (time.time() - t0) * 1e6 / max(len(rows) * len(traces), 1)
    _save("fig2_ensemble_combos", rows)
    for k, v in rows.items():
        _emit(f"fig2/{k}", us, f"AP50={v}")
    return rows


# ---------------------------------------------------------------------------
# Table II: baselines + Armol variants (+ Fig. 6/7 curves)
# ---------------------------------------------------------------------------

def _agent_row(history):
    last = history[-1]
    return {"mAP": round(last["map"], 2), "AP50": round(last["ap50"], 2),
            "cost": round(last["cost"], 3), "counts": last["counts"]}


def bench_baselines(traces=None):
    from repro.core.loops import (ensembleN_policy, evaluate_policy,
                                  random1_policy, randomN_policy, run_ppo,
                                  run_off_policy, upper_bound)
    from repro.core.ppo import PPO, PPOConfig
    from repro.core.sac import SAC, SACConfig
    from repro.core.td3 import TD3, TD3Config
    from repro.federation.env import ArmolEnv
    traces = traces if traces is not None else _traces()
    rows = {}
    histories = {}
    t0 = time.time()

    env = ArmolEnv(traces, mode="gt", beta=0.0, seed=1)
    for name, pol in (("Random-1", random1_policy(env, seed=0)),
                      ("Random-N", randomN_policy(env, seed=0)),
                      ("Ensemble-N", ensembleN_policy(env))):
        r = evaluate_policy(pol, env)
        rows[name] = {"mAP": round(r["map"], 2),
                      "AP50": round(r["ap50"], 2),
                      "cost": round(r["cost"], 3), "counts": r["counts"]}

    def kw():
        return dict(epochs=EPOCHS, steps_per_epoch=STEPS, batch_size=256,
                    start_steps=min(STEPS, 500),
                    update_after=min(STEPS, 300), update_every=50,
                    update_iters=50, log=None)

    sac = SAC(SACConfig(state_dim=env.state_dim,
                        n_providers=env.n_providers, alpha=0.02))
    histories["Armol-w/ gt"] = run_off_policy(sac, env, **kw())
    rows["Armol-w/ gt"] = _agent_row(histories["Armol-w/ gt"])

    env_nogt = ArmolEnv(traces, mode="nogt", beta=-0.1, seed=1)
    sac2 = SAC(SACConfig(state_dim=env_nogt.state_dim,
                         n_providers=env_nogt.n_providers, alpha=0.02))
    histories["Armol-w/o gt"] = run_off_policy(sac2, env_nogt, **kw())
    rows["Armol-w/o gt"] = _agent_row(histories["Armol-w/o gt"])

    ppo = PPO(PPOConfig(state_dim=env.state_dim,
                        n_providers=env.n_providers))
    histories["Armol-PPO"] = run_ppo(ppo, env, epochs=EPOCHS,
                                     steps_per_epoch=STEPS, log=None)
    rows["Armol-PPO"] = _agent_row(histories["Armol-PPO"])

    td3 = TD3(TD3Config(state_dim=env.state_dim,
                        n_providers=env.n_providers))
    histories["Armol-TD3"] = run_off_policy(td3, env, **kw())
    rows["Armol-TD3"] = _agent_row(histories["Armol-TD3"])

    ub = upper_bound(env)
    rows["Upper Bound"] = {"mAP": round(ub["map"], 2),
                           "AP50": round(ub["ap50"], 2),
                           "cost": round(ub["cost"], 3),
                           "counts": ub["counts"]}
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)
    _save("table2_baselines", rows)
    _save("fig6_training_curves", histories)
    for k, v in rows.items():
        _emit(f"table2/{k}", us,
              f"mAP={v['mAP']};AP50={v['AP50']};cost={v['cost']}")
    return rows


# ---------------------------------------------------------------------------
# Table III: scalability to 10 providers (1023 actions)
# ---------------------------------------------------------------------------

def bench_scalability():
    from repro.core.loops import evaluate_policy, run_off_policy, \
        ensembleN_policy
    from repro.core.sac import SAC, SACConfig
    from repro.ensemble.metrics import ap50
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import scalability_providers
    from repro.federation.traces import generate_traces
    t0 = time.time()
    traces = generate_traces(scalability_providers(), IMAGES, seed=0)
    gts = {i: g for i, g in enumerate(traces.gts)}
    rows = {}
    for pi, p in enumerate(traces.providers):
        dts = {i: traces.dets[i][pi] for i in range(len(traces))}
        rows[f"MLaaS {pi}"] = {"AP50": round(100 * ap50(dts, gts), 2),
                               "cost": 1.0}
    env = ArmolEnv(traces, mode="gt", beta=0.0, seed=1)
    r = evaluate_policy(ensembleN_policy(env), env)
    rows["All"] = {"AP50": round(r["ap50"], 2), "cost": round(r["cost"], 2)}
    sac = SAC(SACConfig(state_dim=env.state_dim,
                        n_providers=env.n_providers, alpha=0.02))
    hist = run_off_policy(sac, env, epochs=EPOCHS, steps_per_epoch=STEPS,
                          batch_size=256, start_steps=min(STEPS, 500),
                          update_after=min(STEPS, 300), update_every=50,
                          update_iters=50, log=None)
    rows["Armol"] = {"AP50": round(hist[-1]["ap50"], 2),
                     "cost": round(hist[-1]["cost"], 3)}
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)
    _save("table3_scalability", rows)
    _save("fig8_training_curve_10p", hist)
    for k, v in rows.items():
        _emit(f"table3/{k}", us, f"AP50={v['AP50']};cost={v['cost']}")
    return rows


# ---------------------------------------------------------------------------
# Subset-evaluation core: cached/batched vs the seed per-pair path
# ---------------------------------------------------------------------------

def bench_subset_cache():
    """Upper-bound-style enumeration of all 2^N - 1 subsets per test image
    (paper Algo. 2 / Tab. III regime) through the memoized
    ``SubsetEvaluationCore`` vs the frozen seed implementation
    (``benchmarks/seed_reference.py``).  Also reports the warm-cache pass
    (every (image, subset) pair already memoized — the steady state of a
    multi-epoch training run).  Per-image interleaving keeps the
    comparison fair on noisy shared machines.
    """
    sys.path.insert(0, os.path.dirname(__file__))
    from seed_reference import seed_ensemble_detections, seed_image_ap50
    from repro.core.loops import enumeration_actions
    from repro.federation.evaluation import SubsetEvaluationCore
    from repro.federation.providers import scalability_providers
    from repro.federation.traces import generate_traces

    n_prov = 7
    n_images = min(IMAGES, 60)
    traces = generate_traces(scalability_providers()[:n_prov], n_images,
                             seed=0)
    actions = enumeration_actions(n_prov)
    core = SubsetEvaluationCore(traces)
    masks = [core.mask_of(a) for a in actions]
    n_pairs = n_images * len(actions)

    seed_s = cached_s = 0.0
    mismatches = 0
    max_ap_diff = 0.0
    for img in range(n_images):
        gt = traces.gts[img]
        t0 = time.time()
        best_v, best_a = -1.0, None
        for a in actions:
            sel = [traces.dets[img][i] for i in range(n_prov) if a[i] > 0.5]
            v = seed_image_ap50(seed_ensemble_detections(sel), gt)
            if v > best_v:
                best_v, best_a = v, a
        seed_s += time.time() - t0
        t0 = time.time()
        best_m, best_vc = core.best_subset(img, masks)
        cached_s += time.time() - t0
        max_ap_diff = max(max_ap_diff, abs(best_v - best_vc))
        if core.mask_of(best_a) != best_m:
            mismatches += 1
    t0 = time.time()
    for img in range(n_images):
        core.best_subset(img, masks)
    warm_s = time.time() - t0

    out = {"n_providers": n_prov, "n_actions": len(actions),
           "n_images": n_images, "n_pairs": n_pairs,
           "seed_s": round(seed_s, 3), "cached_cold_s": round(cached_s, 3),
           "cached_warm_s": round(warm_s, 4),
           "speedup_cold": round(seed_s / max(cached_s, 1e-9), 2),
           "speedup_warm": round(seed_s / max(warm_s, 1e-9), 1),
           "best_subset_mismatches": mismatches,
           "max_best_ap50_diff": max_ap_diff,
           "cache": core.cache_sizes(), "stats": dict(core.stats)}
    assert mismatches == 0, \
        f"cached upper-bound picked different subsets on {mismatches} images"
    _save("subset_cache", out)
    _emit("subset_cache/seed", 1e6 * seed_s / n_pairs,
          f"total={out['seed_s']}s")
    _emit("subset_cache/cached_cold", 1e6 * cached_s / n_pairs,
          f"speedup={out['speedup_cold']}x")
    _emit("subset_cache/cached_warm", 1e6 * warm_s / n_pairs,
          f"speedup={out['speedup_warm']}x")
    return out


# ---------------------------------------------------------------------------
# Full-lattice subset evaluation vs the memoized per-bitmask loop
# ---------------------------------------------------------------------------

def bench_lattice():
    """One vectorized pass over all 2^N - 1 subsets per image
    (``evaluate_lattice``) vs the memoized per-bitmask enumeration
    (``best_subset``), at N in {5, 7, 10}, plus a first N=12 exact
    oracle: ``upper_bound`` end to end — 4095 subsets per test image.

    Both paths start COLD every round (fresh cores, so IoU tables and
    memo rebuild) because the lattice's win IS the cold path — warm,
    both are memo lookups.  Rounds interleave loop/lattice via the
    shared best-of harness so machine noise hits both, and the
    regression gate (tools/check_bench.py) checks the speedup RATIOS at
    N=7 and N=10, which cancel absolute machine speed.  The N=12 loop
    time is projected from a strided subsample of masks (popcount-order
    stride keeps the ensemble-size mixture representative) — running
    the full loop at N=12 is exactly what the lattice exists to avoid.
    """
    from repro.core.loops import upper_bound
    from repro.federation.env import ArmolEnv
    from repro.federation.evaluation import SubsetEvaluationCore, \
        popcount_masks
    from repro.federation.providers import lattice_stress_providers
    from repro.federation.traces import generate_traces

    rounds = int(os.environ.get("REPRO_BENCH_ROUNDS", "3"))
    n_images = min(IMAGES, 12)
    out = {"n_images": n_images, "rounds": rounds, "sizes": {}}
    for n_prov in (5, 7, 10):
        traces = generate_traces(lattice_stress_providers(n_prov),
                                 n_images, seed=0)
        masks = popcount_masks(n_prov)
        picks = {}

        def run_loop():
            core = SubsetEvaluationCore(traces)
            picks["loop"] = [core.best_subset(i, masks)
                             for i in range(n_images)]

        def run_lattice():
            core = SubsetEvaluationCore(traces)
            rows = []
            for i in range(n_images):
                lat = core.evaluate_lattice(i)
                j = int(np.argmax(lat.ap))
                rows.append((int(lat.masks[j]), float(lat.ap[j])))
            picks["lattice"] = rows

        loop_s, lat_s = _best_of(run_loop, run_lattice, rounds=rounds,
                                 warmup=False)
        mismatches = sum(a != b for a, b in zip(picks["loop"],
                                                picks["lattice"]))
        assert mismatches == 0, \
            f"lattice argmax disagrees with best_subset on {mismatches} " \
            f"images at N={n_prov}"
        row = {"n_subsets": len(masks), "loop_s": round(loop_s, 3),
               "lattice_s": round(lat_s, 3),
               "speedup": round(loop_s / max(lat_s, 1e-9), 2)}
        out["sizes"][f"n{n_prov}"] = row
        _emit(f"lattice/n{n_prov}",
              1e6 * lat_s / (n_images * len(masks)),
              f"loop={row['loop_s']}s;lattice={row['lattice_s']}s;"
              f"speedup={row['speedup']}x")
    out["speedup_n7"] = out["sizes"]["n7"]["speedup"]
    out["speedup_n10"] = out["sizes"]["n10"]["speedup"]

    # N=12: the first exact oracle at 4095 subsets/image, end to end
    n12 = 12
    tr12 = generate_traces(lattice_stress_providers(n12), n_images, seed=0)
    env = ArmolEnv(tr12, mode="gt", beta=0.0, seed=1)
    t0 = time.time()
    ub = upper_bound(env)
    ub_s = time.time() - t0
    masks12 = popcount_masks(n12)
    sample = masks12[::64]              # strided over popcount order
    core = SubsetEvaluationCore(tr12)
    img0 = int(env.test_idx[0])
    core.precompute([img0])
    t0 = time.time()
    for m in sample:
        core.ap50(img0, m)
    loop12_proj = ((time.time() - t0) / len(sample)
                   * len(masks12) * len(env.test_idx))
    out["n12_oracle"] = {
        "n_subsets": len(masks12), "test_images": len(env.test_idx),
        "upper_bound_s": round(ub_s, 2),
        "loop_projected_s": round(loop12_proj, 1),
        "projected_speedup": round(loop12_proj / max(ub_s, 1e-9), 1),
        "ub_ap50": round(ub["ap50"], 2), "ub_cost": round(ub["cost"], 3)}
    _emit("lattice/n12_oracle", 1e6 * ub_s / max(len(env.test_idx), 1),
          f"upper_bound={out['n12_oracle']['upper_bound_s']}s;"
          f"loop_projected={out['n12_oracle']['loop_projected_s']}s;"
          f"ap50={out['n12_oracle']['ub_ap50']}")
    _save("lattice", out)
    return out


# ---------------------------------------------------------------------------
# Training drivers: multi-lane batched vs sequential reference steps/sec
# ---------------------------------------------------------------------------

def bench_train_driver():
    """Off-policy (SAC) and on-policy (PPO) training throughput of the
    multi-lane drivers (``step_lanes`` + ``add_batch`` + fused
    ``lax.scan`` update blocks) vs the frozen sequential references, at
    REPRO_BENCH_LANES lanes (default 8).  Subset-evaluation tables are
    prewarmed and both paths get a short compile warmup, so the numbers
    compare steady-state driver overhead, not jit or IoU-table cost.
    """
    from repro.core.loops import (run_off_policy, run_offpolicy_sequential,
                                  run_ppo, run_ppo_sequential)
    from repro.core.ppo import PPO, PPOConfig
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces

    lanes = int(os.environ.get("REPRO_BENCH_LANES", "8"))
    n_images = min(IMAGES, 120)
    steps = STEPS
    traces = generate_traces(default_providers(), n_images, seed=0)
    env = ArmolEnv(traces, mode="gt", beta=-0.03, seed=1)
    env.core.precompute(np.arange(len(traces)))

    # paper-scale selector heads (3 providers need no 256x256 MLPs); the
    # benchmark compares driver overhead, so the gradient-step compute —
    # identical math on both paths — is kept at the problem's actual size
    def sac():
        return SAC(SACConfig(state_dim=env.state_dim,
                             n_providers=env.n_providers, alpha=0.02,
                             hidden=(32, 32)))

    def ppo():
        return PPO(PPOConfig(state_dim=env.state_dim,
                             n_providers=env.n_providers, hidden=(32, 32)))

    # start_steps/update_after are lane-multiples so both paths run the
    # same shapes end-to-end (no mixed explore/policy partial batches)
    burn = min(12 * lanes, steps // 4 - steps // 4 % lanes)
    kw = dict(epochs=1, steps_per_epoch=steps, batch_size=64,
              start_steps=burn, update_after=burn, update_every=50,
              update_iters=10, log=None, seed=0)

    def timed(driver, agent_fn, buffer_fn=None, **dkw):
        """Replay the driver with identical seeds: the first pass jits
        every shape and memoizes the exact (image, mask) stream the
        deterministic seeds repeat; the later passes measure steady-state
        driver throughput (min of 3 — this is a shared, noisy machine).
        The per-epoch test-episode evaluation is timed separately and
        subtracted: it is the identical epilogue on both paths, not part
        of the experience-collection/update loop under comparison.
        ``buffer_fn`` builds a fresh replay buffer per pass (buffers are
        stateful, so passes must not share one)."""
        from repro.core.loops import agent_policy, evaluate_policy
        dt = float("inf")
        for i in range(4):
            env.rng = np.random.default_rng(41)
            kw_i = dict(dkw, buffer=buffer_fn()) if buffer_fn else dkw
            t0 = time.time()
            hist = driver(agent_fn(), env, **kw_i)
            if i > 0:
                dt = min(dt, time.time() - t0)
            agent = agent_fn.last
        ev = min(_best_of(lambda: evaluate_policy(agent_policy(agent),
                                                  env)), dt / 2)
        return hist, dt - dkw.get("epochs", 1) * ev

    class _remember:
        def __init__(self, fn):
            self.fn = fn

        def __call__(self):
            self.last = self.fn()
            return self.last

    def timed_lanes(driver, agent_fn, variants, **dkw):
        """Like ``timed`` but interleaves passes of several buffer
        variants of the same driver, so a transient load spike on this
        shared machine hits all variants instead of biasing whichever
        one it landed on — the host-vs-device ratio is the gated metric
        and must not depend on measurement order."""
        from repro.core.loops import agent_policy, evaluate_policy
        dts = [float("inf")] * len(variants)
        hists = [None] * len(variants)
        for i in range(4):
            for j, buffer_fn in enumerate(variants):
                env.rng = np.random.default_rng(41)
                kw_i = dict(dkw, buffer=buffer_fn()) if buffer_fn else dkw
                t0 = time.time()
                hists[j] = driver(agent_fn(), env, **kw_i)
                if i > 0:
                    dts[j] = min(dts[j], time.time() - t0)
        agent = agent_fn.last
        ev = min(_best_of(lambda: evaluate_policy(agent_policy(agent),
                                                  env)), min(dts) / 2)
        e = dkw.get("epochs", 1)
        return [(h, dt - e * ev) for h, dt in zip(hists, dts)]

    # device-resident lane: jax-PRNG index draws, on-device feature
    # assembly from the env's feature table, no per-block metric sync
    def dev_buf():
        from repro.core.device_replay import DeviceReplayBuffer
        return DeviceReplayBuffer(100_000, env.state_dim, env.n_providers,
                                  seed=0, index_mode="jax",
                                  feature_table=env.device_features())

    sac, ppo = _remember(sac), _remember(ppo)
    h_seq, seq_s = timed(run_offpolicy_sequential, sac, **kw)
    (h_bat, bat_s), (h_dev, dev_s) = timed_lanes(
        run_off_policy, sac, [None, dev_buf], lanes=lanes, **kw)
    sps_seq = h_seq[-1]["steps"] / max(seq_s, 1e-9)
    sps_bat = h_bat[-1]["steps"] / max(bat_s, 1e-9)
    sps_dev = h_dev[-1]["steps"] / max(dev_s, 1e-9)

    _, ppo_seq_s = timed(run_ppo_sequential, ppo, epochs=1,
                         steps_per_epoch=steps, log=None)
    _, ppo_bat_s = timed(run_ppo, ppo, lanes=lanes, epochs=1,
                         steps_per_epoch=steps, log=None)
    ppo_steps = -(-steps // lanes) * lanes
    ppo_sps_seq = steps / max(ppo_seq_s, 1e-9)
    ppo_sps_bat = ppo_steps / max(ppo_bat_s, 1e-9)

    out = {"lanes": lanes, "n_images": n_images, "steps_per_epoch": steps,
           "offpolicy": {
               "sequential_s": round(seq_s, 3), "batched_s": round(bat_s, 3),
               "device_s": round(dev_s, 3),
               "sequential_steps_per_s": round(sps_seq, 1),
               "batched_steps_per_s": round(sps_bat, 1),
               "device_steps_per_s": round(sps_dev, 1),
               "speedup": round(sps_bat / max(sps_seq, 1e-9), 2),
               "speedup_device_vs_host": round(sps_dev / max(sps_bat, 1e-9),
                                               2),
               "final_ap50_sequential": round(h_seq[-1]["ap50"], 2),
               "final_ap50_batched": round(h_bat[-1]["ap50"], 2),
               "final_ap50_device": round(h_dev[-1]["ap50"], 2)},
           "ppo": {
               "sequential_s": round(ppo_seq_s, 3),
               "batched_s": round(ppo_bat_s, 3),
               "sequential_steps_per_s": round(ppo_sps_seq, 1),
               "batched_steps_per_s": round(ppo_sps_bat, 1),
               "speedup": round(ppo_sps_bat / max(ppo_sps_seq, 1e-9), 2)}}
    _save("train_driver", out)
    _emit("train_driver/offpolicy_sequential", 1e6 / max(sps_seq, 1e-9),
          f"steps_per_s={out['offpolicy']['sequential_steps_per_s']}")
    _emit("train_driver/offpolicy_batched", 1e6 / max(sps_bat, 1e-9),
          f"steps_per_s={out['offpolicy']['batched_steps_per_s']};"
          f"speedup={out['offpolicy']['speedup']}x;lanes={lanes}")
    _emit("train_driver/offpolicy_device", 1e6 / max(sps_dev, 1e-9),
          f"steps_per_s={out['offpolicy']['device_steps_per_s']};"
          f"speedup_device_vs_host="
          f"{out['offpolicy']['speedup_device_vs_host']}x;lanes={lanes}")
    _emit("train_driver/ppo_sequential", 1e6 / max(ppo_sps_seq, 1e-9),
          f"steps_per_s={out['ppo']['sequential_steps_per_s']}")
    _emit("train_driver/ppo_batched", 1e6 / max(ppo_sps_bat, 1e-9),
          f"steps_per_s={out['ppo']['batched_steps_per_s']};"
          f"speedup={out['ppo']['speedup']}x;lanes={lanes}")
    return out


# ---------------------------------------------------------------------------
# Serving: sequential handle vs batched handle_many vs async micro-batching
# ---------------------------------------------------------------------------

def bench_serving():
    """Requests/sec and p50/p99 latency of the federation serving paths
    under a Poisson open-loop client: per-request ``handle``, batched
    ``handle_many``, and the micro-batching ``AsyncFederationService``.

    The offered load is ``REPRO_BENCH_LAMBDA_X`` (default 8) times the
    measured sequential capacity, so every server is saturated and the
    throughput numbers compare capacities (the sequential server's
    latency diverges — that is the story).  Sync paths are measured on a
    virtual queue clock (real compute, simulated arrivals); the async
    service is driven in real time by a submitter thread.  All paths run
    warm (tables + memo + every jit flush shape prewarmed — this
    benchmarks steady-state serving), the three paths' runs are
    interleaved over ``REPRO_BENCH_ROUNDS`` rounds with each path keeping
    its best round (shared noisy machines), and the regression gate
    (tools/check_bench.py) gates on the capacity ratios, which cancel
    machine speed.
    """
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces
    from repro.serving.async_service import AsyncFederationService
    from repro.serving.federation_service import FederationService

    n_images = min(IMAGES, 120)
    n_reqs = int(os.environ.get("REPRO_BENCH_REQUESTS", "600"))
    max_batch = int(os.environ.get("REPRO_BENCH_MAX_BATCH", "16"))
    workers = int(os.environ.get("REPRO_BENCH_WORKERS", "4"))
    max_wait_ms = float(os.environ.get("REPRO_BENCH_MAX_WAIT_MS", "2.0"))
    lambda_x = float(os.environ.get("REPRO_BENCH_LAMBDA_X", "8.0"))

    traces = generate_traces(default_providers(), n_images, seed=0)
    env = ArmolEnv(traces, mode="gt", beta=0.0, seed=1)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(32, 32)))
    svc = FederationService(env, agent)
    rng = np.random.default_rng(0)
    reqs = [int(i) for i in rng.integers(0, n_images, n_reqs)]

    rounds = int(os.environ.get("REPRO_BENCH_ROUNDS", "3"))

    # warm: IoU tables, (image, mask) memo, and the jit cache for every
    # flush shape the open-loop client can produce (the batched forward
    # compiles once per distinct batch size)
    env.core.precompute(np.arange(n_images))
    for i in range(n_images):
        svc.handle(i)
    for b in range(1, max_batch + 1):
        svc.handle_many(list(range(min(b, n_images))))

    # sequential capacity sets the offered load
    calib = reqs[:100]
    t0 = time.time()
    for i in calib:
        svc.handle(i)
    seq_cap = len(calib) / (time.time() - t0)
    lam = lambda_x * seq_cap
    arrivals = rng.exponential(1.0 / lam, n_reqs).cumsum()

    def pct(lat):
        return (round(float(np.percentile(lat, 50)) * 1e3, 2),
                round(float(np.percentile(lat, 99)) * 1e3, 2))

    def run_sequential():
        # per-request handle on a virtual queue clock
        clock, lat = 0.0, np.zeros(n_reqs)
        for i, img in enumerate(reqs):
            start = max(arrivals[i], clock)
            t0 = time.time()
            svc.handle(img)
            clock = start + (time.time() - t0)
            lat[i] = clock - arrivals[i]
        return n_reqs / (clock - arrivals[0]), lat, None

    def run_many():
        # micro-batched handle_many on the same virtual clock: each flush
        # takes whatever has arrived, up to max_batch
        clock, lat, i = arrivals[0], np.zeros(n_reqs), 0
        while i < n_reqs:
            if arrivals[i] > clock:
                clock = arrivals[i]
            j = i + np.searchsorted(arrivals[i:], clock, side="right")
            j = min(j, i + max_batch, n_reqs)
            t0 = time.time()
            svc.handle_many(reqs[i:j])
            clock += time.time() - t0
            lat[i:j] = clock - arrivals[i:j]
            i = j
        return n_reqs / (clock - arrivals[0]), lat, None

    def run_async():
        # the real thing: concurrent submitter thread, real wall clock
        import threading
        with AsyncFederationService(env, agent, max_batch=max_batch,
                                    max_wait_ms=max_wait_ms,
                                    workers=workers) as asvc:
            asvc.handle_many(list(range(n_images)))     # warm the shards
            asvc.reset_stats()      # report only the measured window
            done = np.zeros(n_reqs)
            futures = [None] * n_reqs

            def record(i):
                def cb(_fut):
                    done[i] = time.monotonic()
                return cb

            base = time.monotonic()

            def submit_all():
                # coarse pacing: sleep only when >2ms ahead of schedule
                # (per-request sub-ms sleeps overshoot and would throttle
                # the offered load below lambda), then submit all due
                for i, img in enumerate(reqs):
                    delay = base + arrivals[i] - time.monotonic()
                    if delay > 2e-3:
                        time.sleep(delay)
                    futures[i] = asvc.submit(img)
                    futures[i].add_done_callback(record(i))

            sub = threading.Thread(target=submit_all)
            t0 = time.monotonic()
            sub.start()
            sub.join()
            while not np.all(done > 0):
                time.sleep(0.001)
            for f in futures:       # surface request failures, don't
                f.result()          # report them as completions
            lat = done - base - arrivals
            rps = n_reqs / (done.max() - t0)
            return rps, lat, (dict(asvc.stats), asvc.mean_flush_size())

    # interleave the three paths; each keeps its best round
    best = {}
    for _ in range(rounds):
        for name, fn in (("sequential", run_sequential),
                         ("handle_many", run_many), ("async", run_async)):
            r = fn()
            if name not in best or r[0] > best[name][0]:
                best[name] = r
    seq_rps, seq_lat, _ = best["sequential"]
    many_rps, many_lat, _ = best["handle_many"]
    async_rps, async_lat, (stats, mean_flush) = best["async"]
    seq_p50, seq_p99 = pct(seq_lat)
    many_p50, many_p99 = pct(many_lat)
    async_p50, async_p99 = pct(async_lat)

    out = {"n_images": n_images, "requests": n_reqs,
           "max_batch": max_batch, "workers": workers,
           "max_wait_ms": max_wait_ms,
           "offered_rps": round(lam, 1),
           "sequential": {"rps": round(seq_rps, 1), "p50_ms": seq_p50,
                          "p99_ms": seq_p99},
           "handle_many": {"rps": round(many_rps, 1), "p50_ms": many_p50,
                           "p99_ms": many_p99},
           "async": {"rps": round(async_rps, 1), "p50_ms": async_p50,
                     "p99_ms": async_p99,
                     "mean_flush": round(mean_flush, 1),
                     "flushes": stats["flushes"],
                     "max_flush": stats["max_flush"]},
           "speedup_async_vs_handle": round(async_rps / max(seq_rps, 1e-9),
                                            2),
           "speedup_many_vs_handle": round(many_rps / max(seq_rps, 1e-9),
                                           2)}
    _save("serving", out)
    _emit("serving/handle", 1e6 / max(seq_rps, 1e-9),
          f"rps={out['sequential']['rps']};p50={seq_p50}ms;p99={seq_p99}ms")
    _emit("serving/handle_many", 1e6 / max(many_rps, 1e-9),
          f"rps={out['handle_many']['rps']};p50={many_p50}ms;"
          f"p99={many_p99}ms;speedup={out['speedup_many_vs_handle']}x")
    _emit("serving/async", 1e6 / max(async_rps, 1e-9),
          f"rps={out['async']['rps']};p50={async_p50}ms;p99={async_p99}ms;"
          f"speedup={out['speedup_async_vs_handle']}x;"
          f"mean_flush={out['async']['mean_flush']}")
    return out


# ---------------------------------------------------------------------------
# Serving shards: thread vs process backend capacity at W in {1, 2, 4}
# ---------------------------------------------------------------------------

def bench_serving_mp():
    """Saturated-drain capacity of ``AsyncFederationService`` with thread
    vs process shard backends at W in {1, 2, 4}.

    The request stream is one permutation of DISTINCT images per round,
    with the shard caches invalidated between rounds: every request pays
    the real production cost of a never-seen image (IoU table build +
    ensemble assembly), which is exactly the work the GIL serializes on
    the thread backend and worker processes parallelize.  jit shapes and
    worker processes stay warm across rounds — this measures steady-state
    serving capacity, not spawn or compile cost.  7 providers (the
    Tab.-III scalability roster) keep per-request assembly realistic.

    At each W the thread and process services are alive TOGETHER and
    their drain rounds interleave (thread, process, thread, ...), so a
    load spike on a shared machine hits both backends, not one; each
    config keeps its best of ``REPRO_BENCH_ROUNDS`` rounds and the
    regression gate compares process/thread RATIOS at equal W, which
    cancel absolute machine speed.
    """
    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import scalability_providers
    from repro.federation.traces import generate_traces
    from repro.serving.async_service import AsyncFederationService

    n_prov = 7
    n_images = min(IMAGES, 240)
    max_batch = int(os.environ.get("REPRO_BENCH_MAX_BATCH", "16"))
    rounds = int(os.environ.get("REPRO_BENCH_ROUNDS", "5"))
    widths = (1, 2, 4)

    traces = generate_traces(scalability_providers()[:n_prov], n_images,
                             seed=0)
    env = ArmolEnv(traces, mode="gt", beta=0.0, seed=1)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(32, 32)))
    reqs = [int(i) for i in
            np.random.default_rng(0).permutation(n_images)]

    def drain(svc) -> float:
        # cold caches, warm everything else: each request re-pays table
        # build + assembly, never jit or spawn
        svc.core.invalidate_images(reqs)
        svc.reset_stats()
        t0 = time.time()
        futs = [svc.submit(i) for i in reqs]
        for f in futs:
            f.result()
        return len(reqs) / (time.time() - t0)

    out = {"n_providers": n_prov, "n_images": n_images,
           "max_batch": max_batch, "rounds": rounds,
           "backends": {"thread": {}, "process": {}}}
    for w in widths:
        svcs = {}
        try:
            for backend in ("thread", "process"):
                svc = AsyncFederationService(
                    env, agent, max_batch=max_batch, max_wait_ms=2.0,
                    workers=w, transport=backend)
                svc.handle(reqs[0])          # single-request jit shape
                svc.handle_many(reqs)        # batched jit shape + warm run
                svcs[backend] = svc
            best = {"thread": 0.0, "process": 0.0}
            for _ in range(rounds):
                for backend, svc in svcs.items():
                    best[backend] = max(best[backend], drain(svc))
            for backend, svc in svcs.items():
                out["backends"][backend][f"w{w}"] = {
                    "rps": round(best[backend], 1),
                    "mean_flush": round(svc.mean_flush_size(), 1)}
        finally:
            for svc in svcs.values():
                svc.close()
    for w in widths:
        t = out["backends"]["thread"][f"w{w}"]["rps"]
        p = out["backends"]["process"][f"w{w}"]["rps"]
        out[f"speedup_process_vs_thread_w{w}"] = round(p / max(t, 1e-9), 2)
    _save("serving_mp", out)
    for backend in ("thread", "process"):
        for w in widths:
            r = out["backends"][backend][f"w{w}"]
            _emit(f"serving_mp/{backend}_w{w}", 1e6 / max(r["rps"], 1e-9),
                  f"rps={r['rps']};mean_flush={r['mean_flush']}")
    for w in widths:
        _emit(f"serving_mp/speedup_w{w}", 0.0,
              f"process_vs_thread={out[f'speedup_process_vs_thread_w{w}']}x")
    return out


def bench_serving_socket():
    """Multi-host serving plane: socket shard hosts vs process shard
    workers, the HTTP front door's SLO, and host-kill degradation.

    Three sections, all sized by REPRO_BENCH_IMAGES/_MAX_BATCH/_ROUNDS:

    ``capacity``  — saturated-drain rps of ``AsyncFederationService``
        with ``transport='socket'`` vs ``transport='process'`` at H in
        {1, 2} hosts/workers, both alive together with interleaved
        rounds (same machine, same run — the gated ratio
        ``speedup_socket_vs_process_h2`` cancels absolute speed).  The
        socket plane pays pickle + TCP framing where the process plane
        pays pickle + pipe, so the ratio is its capacity *overhead*
        check: it must stay near 1.0, and a collapse means a framing or
        locking regression, not a slower machine.

    ``http``      — the same drain pushed through the stdlib HTTP front
        door.  The gated ``modeled_p99_ms`` is the p99 of the MODELED
        request latency (paper latency model + pinned seeds) observed
        over HTTP, which is machine-invariant: the transport may slow a
        run down, but it must never change what the model answers.
        Wall-clock HTTP rps is reported, not gated.

    ``host_kill`` — H=2 socket hosts, one SIGKILLed mid-drain.  Every
        in-flight and subsequent request must still complete
        (``completed_frac`` gated at 1.0) with exactly one host
        condemned — the requeue path, measured, not just unit-tested.
    """
    import signal

    from repro.core.sac import SAC, SACConfig
    from repro.federation.env import ArmolEnv
    from repro.federation.providers import scalability_providers
    from repro.federation.traces import generate_traces
    from repro.serving.async_service import AsyncFederationService
    from repro.serving.http_front import HttpFrontDoor, HttpServingClient

    n_prov = 7
    n_images = min(IMAGES, 240)
    max_batch = int(os.environ.get("REPRO_BENCH_MAX_BATCH", "16"))
    rounds = int(os.environ.get("REPRO_BENCH_ROUNDS", "3"))
    hs = (1, 2)

    traces = generate_traces(scalability_providers()[:n_prov], n_images,
                             seed=0)
    env = ArmolEnv(traces, mode="gt", beta=0.0, seed=1)
    agent = SAC(SACConfig(state_dim=env.state_dim,
                          n_providers=env.n_providers, hidden=(32, 32)))
    reqs = [int(i) for i in
            np.random.default_rng(0).permutation(n_images)]

    def drain(svc) -> float:
        svc.core.invalidate_images(reqs)
        svc.reset_stats()
        t0 = time.time()
        futs = [svc.submit(i) for i in reqs]
        for f in futs:
            f.result()
        return len(reqs) / (time.time() - t0)

    out = {"n_providers": n_prov, "n_images": n_images,
           "max_batch": max_batch, "rounds": rounds,
           "transports": {"process": {}, "socket": {}}}

    # -- capacity: socket vs process, interleaved, best-of --------------
    for h in hs:
        svcs = {}
        try:
            for transport in ("process", "socket"):
                svc = AsyncFederationService(
                    env, agent, max_batch=max_batch, max_wait_ms=2.0,
                    workers=h, transport=transport)
                svc.handle(reqs[0])          # single-request jit shape
                svc.handle_many(reqs)        # batched jit shape + warm run
                svcs[transport] = svc
            best = {"process": 0.0, "socket": 0.0}
            for _ in range(rounds):
                for transport, svc in svcs.items():
                    best[transport] = max(best[transport], drain(svc))
            for transport, svc in svcs.items():
                out["transports"][transport][f"h{h}"] = {
                    "rps": round(best[transport], 1),
                    "mean_flush": round(svc.mean_flush_size(), 1)}
        finally:
            for svc in svcs.values():
                svc.close()
    for h in hs:
        p = out["transports"]["process"][f"h{h}"]["rps"]
        s = out["transports"]["socket"][f"h{h}"]["rps"]
        out[f"speedup_socket_vs_process_h{h}"] = round(s / max(p, 1e-9), 2)

    # -- http: front-door drain, modeled p99 is the SLO -----------------
    with AsyncFederationService(
            env, agent, max_batch=max_batch, max_wait_ms=2.0, workers=2,
            transport="socket") as svc, \
            HttpFrontDoor(svc) as door:
        cli = HttpServingClient(door.url)
        try:
            cli.handle(reqs[0])
            best_rps, lats = 0.0, []
            for _ in range(rounds):
                svc.core.invalidate_images(reqs)
                t0 = time.time()
                results = [f.result()
                           for f in [cli.submit(i) for i in reqs]]
                best_rps = max(best_rps,
                               len(reqs) / (time.time() - t0))
                lats = [r.latency_ms for r in results]
            lats.sort()
            out["http"] = {
                "rps": round(best_rps, 1),
                "modeled_p99_ms": round(
                    lats[min(int(0.99 * len(lats)), len(lats) - 1)], 3),
                "modeled_mean_ms": round(sum(lats) / len(lats), 3)}
        finally:
            cli.close()

    # -- host_kill: SIGKILL one of two hosts mid-drain -------------------
    with AsyncFederationService(
            env, agent, max_batch=max_batch, max_wait_ms=2.0, workers=2,
            transport="socket") as svc:
        svc.handle_many(reqs)
        svc.core.invalidate_images(reqs)
        svc.reset_stats()
        futs = [svc.submit(i) for i in reqs]
        os.kill(svc.core.host_pids()[0], signal.SIGKILL)
        done = sum(1 for f in futs if f.result() is not None)
        out["host_kill"] = {
            "completed_frac": round(done / len(reqs), 3),
            "condemned": svc.transport.condemned,
            "requests_accounted": svc.stats["requests"]}

    _save("serving_socket", out)
    for transport in ("process", "socket"):
        for h in hs:
            r = out["transports"][transport][f"h{h}"]
            _emit(f"serving_socket/{transport}_h{h}",
                  1e6 / max(r["rps"], 1e-9),
                  f"rps={r['rps']};mean_flush={r['mean_flush']}")
    for h in hs:
        _emit(f"serving_socket/speedup_h{h}", 0.0,
              f"socket_vs_process={out[f'speedup_socket_vs_process_h{h}']}x")
    _emit("serving_socket/http", 1e6 / max(out["http"]["rps"], 1e-9),
          f"rps={out['http']['rps']};"
          f"modeled_p99_ms={out['http']['modeled_p99_ms']}")
    _emit("serving_socket/host_kill", 0.0,
          f"completed_frac={out['host_kill']['completed_frac']};"
          f"condemned={out['host_kill']['condemned']}")
    return out


# ---------------------------------------------------------------------------
# Serving scenarios: latency / cost SLOs per regime under provider dynamics
# ---------------------------------------------------------------------------

def bench_serving_scenarios():
    """Poisson open-loop client through non-stationary provider schedules
    on the process-backend serving plane, recording per-regime SLOs.

    One scenario step per request: the serving clock walks the schedule,
    segments swap at flush boundaries, and every request is accounted
    under its segment's fee/latency vectors (a down provider bills 0 and
    costs its outage timeout if the selector still picks it).  Per
    segment we report p50/p99 of the MODELED request latency and the
    mean cost per request — both machine-speed-invariant (they follow
    from the paper's latency/fee model, not the wall clock), which is
    what the regression gate checks.  Wall-clock throughput is reported
    as context, never gated.

    Requests are attributed to segments by arrival index; flush
    boundaries can skew attribution by up to max_batch requests, which
    blurs only the handful of requests at each switch.
    """
    from repro.core.sac import SAC, SACConfig
    from repro.federation.providers import default_providers
    from repro.scenarios import (DynamicProviderPool, NonStationaryArmolEnv,
                                 build_scenario)
    from repro.serving.async_service import AsyncFederationService

    names = [s for s in os.environ.get(
        "REPRO_BENCH_SERVE_SCENARIOS", "provider_outage,price_war"
        ).split(",") if s]
    n_reqs = int(os.environ.get("REPRO_BENCH_REQUESTS", "600"))
    n_images = min(IMAGES, 120)
    max_batch = int(os.environ.get("REPRO_BENCH_MAX_BATCH", "16"))
    workers = int(os.environ.get("REPRO_BENCH_WORKERS", "4"))
    backend = os.environ.get("REPRO_BENCH_SHARD_BACKEND", "process")
    lambda_x = float(os.environ.get("REPRO_BENCH_LAMBDA_X", "4.0"))

    providers = default_providers()
    out = {"config": {"requests": n_reqs, "n_images": n_images,
                      "max_batch": max_batch, "workers": workers,
                      "shard_backend": backend, "scenarios": names}}
    for name in names:
        schedule = build_scenario(name, providers, horizon=n_reqs)
        pool = DynamicProviderPool(providers, schedule, n_images=n_images,
                                   seed=0)
        env = NonStationaryArmolEnv(pool, mode="gt", beta=0.0,
                                    observe_pool=False, seed=1)
        agent = SAC(SACConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers, hidden=(32, 32)))
        rng = np.random.default_rng(0)
        reqs = [int(i) for i in rng.integers(0, n_images, n_reqs)]
        with AsyncFederationService(env, agent, max_batch=max_batch,
                                    max_wait_ms=2.0, workers=workers,
                                    pool=pool, transport=backend) as svc:
            svc.handle(reqs[0])
            svc.handle_many(list(range(n_images)))   # warm shards + jit
            svc.reset_stats()
            svc.set_clock(0)    # warm-up must not consume the schedule
            # offered load: a quick warm drain calibrates capacity, the
            # client then offers lambda_x times that (saturation)
            t0 = time.time()
            for f in [svc.submit(i) for i in reqs[:100]]:
                f.result()
            cap = 100 / (time.time() - t0)
            svc.reset_stats()
            svc.set_clock(0)
            arrivals = rng.exponential(1.0 / (lambda_x * cap),
                                       n_reqs).cumsum()
            base = time.monotonic()
            futures = []
            for i, img in enumerate(reqs):
                delay = base + arrivals[i] - time.monotonic()
                if delay > 2e-3:
                    time.sleep(delay)
                futures.append(svc.submit(img))
            results = [f.result() for f in futures]
            wall_s = time.monotonic() - base
            stats = dict(svc.stats)
            # aggregated over every regime core on every shard: for the
            # process backend this is where segment activity actually
            # lives (the pool's parent-side cache_report stays ~empty)
            shard_stats = dict(svc.core.stats)
            shard_sizes = svc.core.cache_sizes()
        lat = np.asarray([r.latency_ms for r in results])
        cost = np.asarray([r.cost_milli_usd for r in results])
        segs = np.asarray([schedule.segment_index(i)
                           for i in range(n_reqs)])
        seg_rows = []
        for s in sorted(set(segs.tolist())):
            m = segs == s
            view = pool.view_at(int(schedule.segment_range(s)[0]))
            seg_rows.append({
                "seg": int(s), "requests": int(m.sum()),
                "down": [p.name for j, p in enumerate(view.profiles)
                         if not view.active[j]],
                "p50_ms": round(float(np.percentile(lat[m], 50)), 1),
                "p99_ms": round(float(np.percentile(lat[m], 99)), 1),
                "cost_per_request": round(float(cost[m].mean()), 4)})
        row = {
            "segments": seg_rows,
            "worst_p99_ms": round(max(r["p99_ms"] for r in seg_rows), 1),
            "cost_per_request": round(float(cost.mean()), 4),
            "wall_rps": round(n_reqs / wall_s, 1),
            "mean_flush": round(stats["requests"]
                                / max(stats["flushes"], 1), 1),
            "flush_reasons": {k: stats[k] for k in
                              ("flush_full", "flush_timeout",
                               "flush_drain")},
            "shard_cache_sizes": shard_sizes,
            "shard_ens_hit_rate": round(
                shard_stats.get("ens_hits", 0)
                / max(shard_stats.get("ens_hits", 0)
                      + shard_stats.get("ens_misses", 0), 1), 4),
            "pool_cache": pool.cache_report()}
        out[name] = row
        _emit(f"serving_scenarios/{name}", 1e6 * wall_s / n_reqs,
              f"worst_p99={row['worst_p99_ms']}ms;"
              f"cost_per_req={row['cost_per_request']};"
              f"rps={row['wall_rps']};segments={len(seg_rows)}")
    _save("serving_scenarios", out)
    return out


# ---------------------------------------------------------------------------
# Scenario suite: online adaptation under non-stationary provider pools
# ---------------------------------------------------------------------------

def bench_scenarios():
    """Run the built-in non-stationary scenarios end-to-end: SAC adapts
    online (``repro.scenarios.run_online``) through each schedule's regime
    switches, and every segment is scored against the per-segment oracle
    (best active subset per image, ap50 + beta * fee).

    Reported per segment: recovery (agent reward / oracle reward — the
    acceptance bar is >= 0.8 after every switch), regret, AP50, cost, and
    the subset-evaluation cache hit rate the stream saw inside the
    segment (the warm-path health of the pool's segment-keyed caches).
    ``REPRO_BENCH_SCENARIOS`` (comma list) picks scenarios;
    ``REPRO_BENCH_HORIZON`` scales every schedule.
    """
    from repro.core.sac import SAC, SACConfig
    from repro.federation.providers import default_providers
    from repro.scenarios import (BUILTIN_SCENARIOS, DynamicProviderPool,
                                 NonStationaryArmolEnv, build_scenario,
                                 run_online)

    names = [s for s in os.environ.get(
        "REPRO_BENCH_SCENARIOS", ",".join(BUILTIN_SCENARIOS)).split(",")
        if s]
    horizon = int(os.environ.get("REPRO_BENCH_HORIZON", "1600"))
    n_images = min(IMAGES, 120)
    beta = -0.03
    providers = default_providers()
    rows = {}
    post, hits = [], []
    for name in names:
        t0 = time.time()
        schedule = build_scenario(name, providers, horizon=horizon)
        pool = DynamicProviderPool(providers, schedule, n_images=n_images,
                                   seed=0)
        env = NonStationaryArmolEnv(pool, mode="gt", beta=beta,
                                    observe_pool=True, seed=1)
        # gamma=0 because provider selection is a contextual bandit (the
        # next image does not depend on the subset chosen for this one)
        agent = SAC(SACConfig(state_dim=env.state_dim,
                              n_providers=env.n_providers, alpha=0.02,
                              lr=3e-4, gamma=0.0, hidden=(32, 32)))
        res = run_online(agent, env, lanes=4, seed=0, log=None)
        rows[name] = res
        s = res["summary"]
        post += [x["recovery"] for x in res["segments"] if x["seg"] >= 1]
        hits.append(s["mean_cache_hit_rate"])
        _emit(f"scenarios/{name}",
              1e6 * (time.time() - t0) / max(s["steps"], 1),
              f"min_recovery={s['min_recovery_post_switch']};"
              f"segments={s['n_segments']};"
              f"cache_hit={s['mean_cache_hit_rate']}")
    out = {"config": {"horizon": horizon, "n_images": n_images,
                      "beta": beta, "scenarios": names},
           "scenarios": rows,
           "summary": {
               "scenarios_run": len(names),
               "min_recovery": round(min(post), 4) if post else None,
               "mean_recovery":
                   round(float(np.mean(post)), 4) if post else None,
               "mean_cache_hit_rate": round(float(np.mean(hits)), 4)}}
    _save("scenarios", out)
    _emit("scenarios/summary", 0.0,
          f"min_recovery={out['summary']['min_recovery']};"
          f"mean_recovery={out['summary']['mean_recovery']};"
          f"cache_hit={out['summary']['mean_cache_hit_rate']}")
    return out


# ---------------------------------------------------------------------------
# Roofline: achieved vs peak FLOPs/bandwidth of the device-resident paths
# ---------------------------------------------------------------------------

def bench_roofline():
    """Measured roofline points (``repro.roofline.measure``) for the
    device-resident training paths:

      * ``fused_update``  — the SAC ``lax.scan`` update block vs K eager
        update dispatches: wall speedup, plus FLOPs parity from the
        compiled executables' cost analyses.  XLA's cost model counts a
        scanned body ONCE (trip count excluded), so parity is fused-body
        FLOPs over one eager step's FLOPs, ~1.0 — a deterministic,
        machine-invariant check that the fusion drops dispatch overhead,
        not work.
      * ``iou_batch``     — the batched pairwise-IoU path: HLO-derived
        arithmetic intensity places it on the roofline (far below the
        compute/memory knee: it is bandwidth-bound by construction), and
        the CPU-twin vs interpret-mode-Pallas timing records why
        ``resolve_use_kernel`` routes CPU backends to the twin.
      * ``replay_chain``  — T circular writes + one block sample, device
        buffer vs numpy buffer + host->device upload: the same-run
        speedup ratio is the committed gate.

    Achieved FLOP/s and fractions of the TPU-class ``HW`` peaks are
    recorded for interpretation but NEVER gated — this container runs the
    CPU backend, so only same-run ratios and HLO-derived quantities
    (machine-invariant) carry across machines.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import sac as sac_mod
    from repro.core.device_replay import DeviceReplayBuffer
    from repro.core.replay_buffer import ReplayBuffer
    from repro.core.sac import SAC, SACConfig
    from repro.kernels.iou_matrix.kernel import iou_matrix_pallas
    from repro.kernels.iou_matrix.ref import iou_matrix_ref
    from repro.roofline import HW, achieved_point, hlo_cost, timed_best

    rounds = int(os.environ.get("REPRO_BENCH_ROUNDS", "5"))
    rng = np.random.default_rng(0)
    hw = HW()
    out = {"hw": {"peak_flops": hw.peak_flops, "hbm_bw": hw.hbm_bw},
           "backend": jax.default_backend()}

    # --- fused collect->update block vs eager per-step dispatch --------
    D, N, K, B = 80, 3, 10, 64
    cfg = SACConfig(state_dim=D, n_providers=N, hidden=(32, 32))
    agent = SAC(cfg)
    blk = {"s": rng.standard_normal((K, B, D)).astype(np.float32),
           "a": (rng.random((K, B, N)) > 0.5).astype(np.float32),
           "r": rng.standard_normal((K, B)).astype(np.float32),
           "s2": rng.standard_normal((K, B, D)).astype(np.float32),
           "d": np.zeros((K, B), np.float32)}
    single = {k: v[0] for k, v in blk.items()}
    ec = hlo_cost(sac_mod._update, cfg, agent.state,
                  {k: jnp.asarray(v) for k, v in single.items()})
    fc = hlo_cost(sac_mod._update_block, cfg, agent.state,
                  {k: jnp.asarray(v) for k, v in blk.items()})

    def run_fused():
        agent.update_block(blk, sync=False)
        jax.block_until_ready(agent.state)

    def run_eager():
        for _ in range(K):
            agent.update(single)
        jax.block_until_ready(agent.state)

    # interleaved rounds: machine noise hits both candidates
    fused_s, eager_s = _best_of(run_fused, run_eager, rounds=rounds)
    # cost_analysis reports the scan BODY once: scale by K for the whole
    # block's roofline point; body/eager-step ratio is the parity gate
    block_cost = {"flops": K * fc["flops"], "bytes": K * fc["bytes"],
                  "intensity": fc["intensity"]}
    pt = achieved_point(block_cost, fused_s, hw=hw)
    out["fused_update"] = {
        "K": K, "batch": B, "state_dim": D,
        "eager_s_per_block": round(eager_s, 5),
        "fused_s_per_block": round(fused_s, 5),
        "speedup_fused_vs_eager": round(eager_s / max(fused_s, 1e-12), 2),
        "flops_parity": round(fc["flops"] / max(ec["flops"], 1e-9), 4),
        "hlo_flops": block_cost["flops"], "hlo_bytes": block_cost["bytes"],
        "hlo_intensity": round(fc["intensity"], 3),
        "achieved_flops_s": round(pt["achieved_flops_s"], 1),
        "frac_peak_flops": pt["frac_peak_flops"], "bound": pt["bound"]}
    _emit("roofline/fused_update", 1e6 * fused_s,
          f"speedup_vs_eager={out['fused_update']['speedup_fused_vs_eager']}"
          f"x;flops_parity={out['fused_update']['flops_parity']};"
          f"intensity={out['fused_update']['hlo_intensity']}")

    # --- batched pairwise IoU ------------------------------------------
    M, Nb = 256, 512
    a = jnp.asarray(rng.random((M, 4)), jnp.float32)
    b = jnp.asarray(rng.random((Nb, 4)), jnp.float32)
    ref = jax.jit(iou_matrix_ref)
    ic = hlo_cost(ref, a, b)
    ref_s, _ = timed_best(ref, a, b, repeats=rounds)
    pal_s, _ = timed_best(
        lambda x, y: iou_matrix_pallas(x, y, interpret=True), a, b,
        repeats=max(rounds // 2, 1))
    ipt = achieved_point(ic, ref_s, hw=hw)
    out["iou_batch"] = {
        "m": M, "n": Nb,
        "hlo_flops": ic["flops"], "hlo_bytes": ic["bytes"],
        "hlo_intensity": round(ic["intensity"], 3),
        "twin_s": round(ref_s, 6), "pallas_interpret_s": round(pal_s, 4),
        "twin_vs_interpret": round(pal_s / max(ref_s, 1e-12), 1),
        "achieved_bw_s": round(ipt["achieved_bw_s"], 1),
        "frac_peak_bw": ipt["frac_peak_bw"],
        "knee_intensity": round(ipt["knee_intensity"], 1),
        "bound": ipt["bound"]}
    _emit("roofline/iou_batch", 1e6 * ref_s,
          f"intensity={out['iou_batch']['hlo_intensity']};"
          f"bound={out['iou_batch']['bound']};"
          f"twin_vs_interpret={out['iou_batch']['twin_vs_interpret']}x")

    # --- replay write+sample chain: device vs host buffer --------------
    # T ticks per sampled block mirrors the multi-lane driver's regime
    # (update_every=50 at 8 lanes: ~6 collect ticks per update block);
    # the two chains interleave round-by-round so load spikes hit both
    cap, L, T = 4096, 8, 6
    rows = (rng.standard_normal((T, L, D)).astype(np.float32),
            (rng.random((T, L, N)) > 0.5).astype(np.float32),
            rng.standard_normal((T, L)).astype(np.float32),
            rng.standard_normal((T, L, D)).astype(np.float32),
            np.zeros((T, L), np.float32))
    hbuf = ReplayBuffer(cap, D, N, seed=0)
    dbuf = DeviceReplayBuffer(cap, D, N, seed=0, index_mode="jax")

    def chain_host():
        # the host path as run_off_policy drives it: numpy writes, numpy
        # index draw + gather, then the block's host->device upload
        for t in range(T):
            hbuf.add_batch(*(x[t] for x in rows))
        blk = hbuf.sample_block(K, B)
        jax.block_until_ready({k: jnp.asarray(v) for k, v in blk.items()})

    def chain_device():
        for t in range(T):
            dbuf.add_batch(*(x[t] for x in rows))
        jax.block_until_ready(dbuf.sample_block(K, B))

    host_s, dev_s = _best_of(chain_host, chain_device, rounds=rounds)
    out["replay_chain"] = {
        "capacity": cap, "ticks": T, "lanes": L, "K": K, "batch": B,
        "host_s": round(host_s, 5), "device_s": round(dev_s, 5),
        "speedup_device_vs_host": round(host_s / max(dev_s, 1e-12), 2)}
    _emit("roofline/replay_chain", 1e6 * dev_s,
          f"host={out['replay_chain']['host_s']}s;"
          f"device={out['replay_chain']['device_s']}s;speedup_device="
          f"{out['replay_chain']['speedup_device_vs_host']}x")
    _save("roofline", out)
    return out


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (CPU interpret mode — correctness-level timing)
# ---------------------------------------------------------------------------

def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.iou_matrix.kernel import iou_matrix_pallas
    from repro.kernels.iou_matrix.ref import iou_matrix_ref
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_naive
    rng = np.random.default_rng(0)
    rows = {}

    def timeit(fn, *args, n=5):
        fn(*args)                      # compile
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.time() - t0) * 1e6 / n

    a = jnp.asarray(rng.random((256, 4)), jnp.float32)
    b = jnp.asarray(rng.random((512, 4)), jnp.float32)
    rows["iou_pallas_interp"] = timeit(
        lambda x, y: iou_matrix_pallas(x, y, interpret=True), a, b)
    rows["iou_ref"] = timeit(iou_matrix_ref, a, b)

    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    rows["flash_pallas_interp"] = timeit(
        lambda x: flash_attention_pallas(x, x, x, block_q=64, block_k=64,
                                         interpret=True), q)
    rows["flash_ref"] = timeit(lambda x: attention_ref(x, x, x), q)

    xh = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    dt = jnp.asarray(rng.random((1, 128, 2)) * 0.4 + 0.05, jnp.float32)
    A = -jnp.ones((2,), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((1, 128, 8)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((1, 128, 8)), jnp.float32)
    rows["ssd_pallas_interp"] = timeit(
        lambda *xs: ssd_scan(*xs, chunk=32), xh, dt, A, Bm, Cm)
    rows["ssd_ref_naive"] = timeit(ssd_naive, xh, dt, A, Bm, Cm)

    _save("kernel_micro", rows)
    for k, v in rows.items():
        _emit(f"kernels/{k}", v, "interpret-mode; TPU is the target")
    return rows


# ---------------------------------------------------------------------------

def bench_frontier():
    """Cost–accuracy frontier: RL vs cascade vs MCT vs hybrid across the
    scenario suite (``repro.selection.frontier``).  Everything gated is
    seeded/modeled — curves and dominance invariants are deterministic,
    machine-invariant quantities, not timings."""
    import time as _time

    from repro.selection.frontier import run_frontier

    horizon = int(os.environ.get("REPRO_BENCH_FRONTIER_HORIZON", "480"))
    n_images = min(IMAGES, 96)
    t0 = _time.time()
    out = run_frontier(horizon=horizon, n_images=n_images, seed=0,
                       log=None)
    out["wall_s"] = round(_time.time() - t0, 1)
    _save("frontier", out)
    inv = out["invariants"]
    for arm in ("rl", "cascade", "hybrid", "mct"):
        for p in out["frontier"][arm]:
            _emit(f"frontier/{arm}_knob_{p['knob']}", 0.0,
                  f"ap50={p['ap50']} cost={p['cost']}")
    _emit("frontier/invariants", 0.0,
          f"rl>cheapest={inv['rl_dominates_cheapest']} "
          f"rl>all={inv['rl_dominates_all_providers']} "
          f"hybrid>=cascade={inv['hybrid_ge_cascade']}")
    _emit("frontier/paper_point", 0.0,
          f"cost_saving={out['paper_point']['cost_saving_frac']} "
          f"ap50={out['paper_point']['ap50']}")
    return out


BENCHES = {
    "provider_ap": bench_provider_ap,
    "ensemble_combos": bench_ensemble_combos,
    "baselines": bench_baselines,
    "scalability": bench_scalability,
    "subset_cache": bench_subset_cache,
    "lattice": bench_lattice,
    "train_driver": bench_train_driver,
    "serving": bench_serving,
    "serving_mp": bench_serving_mp,
    "serving_socket": bench_serving_socket,
    "serving_scenarios": bench_serving_scenarios,
    "scenarios": bench_scenarios,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
    "frontier": bench_frontier,
}


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    only = [a for a in sys.argv[1:] if not a.startswith("-")]
    names = only or list(BENCHES)
    print("name,us_per_call,derived")
    shared = None
    for n in names:
        fn = BENCHES[n]
        if n in ("provider_ap", "ensemble_combos", "baselines"):
            if shared is None:
                shared = _traces()
            fn(shared)
        else:
            fn()


if __name__ == "__main__":
    main()
