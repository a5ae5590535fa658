#!/usr/bin/env python
"""Benchmark regression gate.

Runs ``benchmarks/run.py <name>`` for each requested benchmark with
``REPRO_RESULTS_DIR`` pointed at a scratch directory (the committed
baselines in ``benchmarks/results/`` are never overwritten), then
compares the fresh numbers against the committed ones and fails on a
warm-path regression larger than the tolerance.

Gated metrics are *ratios* (cached-vs-seed speedups, async-vs-sequential
serving speedups) wherever possible: ratios compare two code paths
measured on the same machine in the same run, so they cancel absolute
machine speed and survive CI-runner heterogeneity.

Usage:
    PYTHONPATH=src python tools/check_bench.py            # default set
    PYTHONPATH=src python tools/check_bench.py serving train_driver

Environment:
    REPRO_BENCH_TOLERANCE   allowed fractional regression before failing
                            (default 0.30).  Noisy/shared runners should
                            raise it, e.g. ``REPRO_BENCH_TOLERANCE=0.6``;
                            set it >= 1 to reduce the gate to a smoke run.
    REPRO_BENCH_RETRIES     extra fresh runs when a gate fails (default
                            1); the best per-metric value across attempts
                            is compared, absorbing transient load spikes
                            on shared machines.
    REPRO_BENCH_IMAGES etc. forwarded to benchmarks/run.py (each bench
                            defaults to its committed baseline's problem
                            size, see BENCH_ENV).
    REPRO_BENCH_SCRATCH     directory for the fresh-run JSONs (default: a
                            throwaway tempdir).  CI points this at a
                            stable path and uploads it as an artifact.

Gated benchmarks include the serving plane: ``serving_mp`` checks the
process-shard backend's capacity ratio over the thread backend at equal
worker counts, ``serving_socket`` checks the socket transport's
capacity ratio over the process transport at H=2 plus the HTTP front
door's modeled-p99 SLO and host-kill requeue completeness, and
``serving_scenarios`` checks per-regime p99 latency and
cost-per-request ceilings of the MODELED accounting under provider
outage / price-war schedules (all machine-speed-invariant).
"""
from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import tempfile
from dataclasses import dataclass
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DIR = os.path.join(REPO, "benchmarks", "results")


@dataclass
class Gate:
    path: str               # dotted path into the result JSON
    direction: str = "higher"   # "higher" or "lower" is better

    def lookup(self, obj):
        for part in self.path.split("."):
            obj = obj[part]
        return float(obj)


# Warm-path metrics gated per benchmark.  All are higher-is-better
# speedup ratios of an optimized path over a reference path in the same
# run (machine-speed invariant).  BENCH_ENV pins each fresh run to the
# same problem size its committed baseline was recorded at (overridable
# from the caller's environment).
GATES = {
    "subset_cache": [Gate("speedup_warm"), Gate("speedup_cold")],
    # full-lattice pass vs the per-bitmask loop, both cold, same run:
    # the ratio cancels machine speed.  N=5 is reported but not gated —
    # at 31 subsets the vectorized pass has little to amortize and its
    # ratio is the noisiest of the three
    "lattice": [Gate("speedup_n7"), Gate("speedup_n10")],
    "serving": [Gate("speedup_async_vs_handle"),
                Gate("speedup_many_vs_handle")],
    "train_driver": [Gate("offpolicy.speedup"), Gate("ppo.speedup"),
                     Gate("offpolicy.speedup_device_vs_host")],
    # machine-invariant roofline gates: FLOPs parity of the scanned
    # update block vs K eager steps and the batched-IoU arithmetic
    # intensity are HLO-derived (deterministic per XLA version); the two
    # speedups are same-run ratios, which cancel absolute machine speed
    "roofline": [Gate("fused_update.flops_parity"),
                 Gate("fused_update.speedup_fused_vs_eager"),
                 Gate("iou_batch.hlo_intensity"),
                 Gate("replay_chain.speedup_device_vs_host")],
    # scenario gates are quality ratios, not timings: post-switch
    # recovery vs the per-segment oracle and the warm-path cache hit
    # rate the stream saw — both machine-speed invariant
    "scenarios": [Gate("summary.min_recovery"),
                  Gate("summary.mean_cache_hit_rate")],
    # process-vs-thread shard capacity ratios at equal W (same machine,
    # same run, interleaved rounds: absolute speed cancels).  w4 is the
    # acceptance headline.  w1 is reported but NOT gated: one worker has
    # no parallelism to win, so its ratio is noise around 1.0 by design
    "serving_mp": [Gate("speedup_process_vs_thread_w4"),
                   Gate("speedup_process_vs_thread_w2")],
    # socket-vs-process shard capacity ratio at H=2 (same machine, same
    # run, interleaved rounds — the TCP plane's framing overhead check;
    # h1 is reported but not gated, one host has nothing to amortize),
    # the HTTP front door's MODELED p99 (paper latency model + pinned
    # seeds: transport may slow a run, it must never change the model's
    # answer), and the host-kill requeue completing every request
    "serving_socket": [Gate("speedup_socket_vs_process_h2"),
                       Gate("http.modeled_p99_ms", "lower"),
                       Gate("host_kill.completed_frac")],
    # SLO ceilings under provider dynamics: worst per-regime p99 of the
    # MODELED request latency and mean cost per request (both follow
    # from the paper's latency/fee model + pinned seeds, so they are
    # machine-speed-invariant; "lower" direction makes the committed
    # baseline a ceiling that REPRO_BENCH_TOLERANCE widens)
    "serving_scenarios": [
        Gate("provider_outage.worst_p99_ms", "lower"),
        Gate("provider_outage.cost_per_request", "lower"),
        Gate("price_war.worst_p99_ms", "lower"),
        Gate("price_war.cost_per_request", "lower")],
    # cost-accuracy frontier dominance invariants: 1.0/0.0 flags (some
    # RL point matches the cheapest single's cost / the all-providers
    # accuracy within the recorded eps margins; hybrid earns >= cascade
    # reward at every shared beta) plus the paper operating point's fee
    # saving at matched accuracy.  Every input is seeded/modeled — no
    # wall clock anywhere — so these are machine-invariant quantities
    "frontier": [Gate("invariants.rl_dominates_cheapest"),
                 Gate("invariants.rl_dominates_all_providers"),
                 Gate("invariants.hybrid_ge_cascade"),
                 Gate("paper_point.cost_saving_frac")],
}

BENCH_ENV = {
    "subset_cache": {"REPRO_BENCH_IMAGES": "50"},
    "lattice": {"REPRO_BENCH_IMAGES": "12",
                "REPRO_BENCH_ROUNDS": "3"},
    "serving": {"REPRO_BENCH_IMAGES": "50"},
    "train_driver": {"REPRO_BENCH_IMAGES": "120"},
    "roofline": {"REPRO_BENCH_ROUNDS": "5"},
    "scenarios": {"REPRO_BENCH_IMAGES": "120",
                  "REPRO_BENCH_HORIZON": "1600"},
    "serving_mp": {"REPRO_BENCH_IMAGES": "240",
                   "REPRO_BENCH_MAX_BATCH": "16",
                   "REPRO_BENCH_ROUNDS": "5"},
    "serving_socket": {"REPRO_BENCH_IMAGES": "240",
                       "REPRO_BENCH_MAX_BATCH": "16",
                       "REPRO_BENCH_ROUNDS": "3"},
    "serving_scenarios": {"REPRO_BENCH_IMAGES": "120",
                          "REPRO_BENCH_REQUESTS": "600",
                          "REPRO_BENCH_MAX_BATCH": "16",
                          "REPRO_BENCH_WORKERS": "4"},
    "frontier": {"REPRO_BENCH_IMAGES": "96",
                 "REPRO_BENCH_FRONTIER_HORIZON": "480"},
}

DEFAULT = ["subset_cache", "serving"]


def run_fresh(name: str, results_dir: str) -> dict:
    env = dict(os.environ)
    env["REPRO_RESULTS_DIR"] = results_dir
    for k, v in BENCH_ENV.get(name, {}).items():
        env.setdefault(k, v)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"), name],
        check=True, env=env, cwd=REPO)
    with open(os.path.join(results_dir, f"{name}.json")) as f:
        return json.load(f)


def check(name: str, fresh: dict, baseline: dict,
          tolerance: float) -> List[str]:
    """Compare per-gate values (dicts keyed by gate path) and report."""
    failures = []
    for gate in GATES[name]:
        base, new = baseline[gate.path], fresh[gate.path]
        if gate.direction == "higher":
            regression = (base - new) / base if base else 0.0
        else:
            regression = (new - base) / base if base else 0.0
        status = "FAIL" if regression > tolerance else "ok"
        print(f"  [{status}] {name}.{gate.path}: baseline={base:g} "
              f"fresh={new:g} regression={100 * regression:+.1f}% "
              f"(tolerance {100 * tolerance:.0f}%)")
        if regression > tolerance:
            failures.append(f"{name}.{gate.path}")
    return failures


def main(argv: List[str]) -> int:
    names = [a for a in argv if not a.startswith("-")] or list(DEFAULT)
    tolerance = float(os.environ.get("REPRO_BENCH_TOLERANCE", "0.30"))
    unknown = [n for n in names if n not in GATES]
    if unknown:
        print(f"no gates defined for: {', '.join(unknown)} "
              f"(gated: {', '.join(GATES)})")
        return 2
    retries = int(os.environ.get("REPRO_BENCH_RETRIES", "1"))
    failures: List[str] = []
    # REPRO_BENCH_SCRATCH pins the fresh-results dir to a known path so
    # CI can upload the measured JSONs as workflow artifacts; unset, a
    # throwaway tempdir keeps local runs tidy
    with contextlib.ExitStack() as stack:
        scratch = os.environ.get("REPRO_BENCH_SCRATCH")
        if scratch:
            os.makedirs(scratch, exist_ok=True)
        else:
            scratch = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="repro-bench-"))
        for name in names:
            base_path = os.path.join(BASELINE_DIR, f"{name}.json")
            if not os.path.exists(base_path):
                print(f"[check_bench] no committed baseline for '{name}' "
                      f"({base_path}); run the benchmark and commit its "
                      f"results/ JSON first")
                return 2
            with open(base_path) as f:
                baseline = json.load(f)
            base_vals = {g.path: g.lookup(baseline) for g in GATES[name]}
            best: dict = {}
            for attempt in range(1 + retries):
                print(f"[check_bench] {name}: running fresh benchmark "
                      f"(attempt {attempt + 1}/{1 + retries}) ...")
                fresh = run_fresh(name, scratch)
                # keep the best value seen per metric: a transient load
                # spike on a shared machine compresses the speedup
                # ratios, it never inflates them
                for gate in GATES[name]:
                    v = gate.lookup(fresh)
                    if gate.path not in best or (
                            (v > best[gate.path])
                            == (gate.direction == "higher")):
                        best[gate.path] = v
                bench_fails = check(name, best, base_vals, tolerance)
                if not bench_fails:
                    break
            failures += bench_fails
    if failures:
        print(f"[check_bench] FAILED: {len(failures)} metric(s) regressed "
              f"beyond {100 * tolerance:.0f}%: {', '.join(failures)}")
        print("[check_bench] on a noisy runner, retry or raise "
              "REPRO_BENCH_TOLERANCE (e.g. REPRO_BENCH_TOLERANCE=0.6)")
        return 1
    print("[check_bench] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
