"""Closed-loop serving cells: ``AsyncFederationService`` on the thread
transport, driven through its ``submit`` API by a fixed population of
callers that each keep one fresh image request outstanding, with no
think time.

It shares the open-loop driver's system under test, check against the
plain reference, end-to-end metrics and summary (``bench/drivers/
serve.py``); only the loop differs.  One generator thread (the caller of
``run``) sends each caller's first request, then, as each request
completes, that caller's next one at once.  A closed-loop request is due
when it is sent, so latency runs from the send; the generator's lag from
an answer to its caller's next send is kept apart, as ``late_ms``.  The
warm-up runs the same loop for ``warmup_s`` on ids the window never
sends.  Ids come from one stream sized at ``id_cap_rate`` requests a
second over warm-up and window; a run that would need more fails, and no
id is sent twice.

The selector is the one deployment serves: its actor weights are drawn
from the configuration's ``selector_seed``, the same in every run, and
only the pool and the requests from the run's seed.  A seed-drawn
untrained selector picks 3.4 to 6.3 of the ten providers a request by
seed, and the host's work per request follows it.
"""
from __future__ import annotations

import gc
import math
import queue
import time

import numpy as np

from bench import common
from bench.drivers import serve
from bench.drivers.serve import (Window, build, check, drain, end_to_end,
                                 outcomes)


def ids_needed(traffic: dict, seconds: float) -> int:
    """The stream's size: ``id_cap_rate`` over warm-up and window."""
    span = float(traffic["warmup_s"]) + float(seconds)
    return int(math.ceil(traffic["id_cap_rate"] * span)) + 1


def closed_loop(svc, stream, callers: int, seconds: float) -> Window:
    """``callers`` requests outstanding from the start until ``seconds``
    have passed; each completion sends its caller's next fresh id at
    once.  Returns after every request sent has been answered (or a
    minute past the close)."""
    w = Window()
    w.late = []
    done = queue.SimpleQueue()

    def finish(k, fut):
        w.mark(k, fut)
        done.put(k)

    def send(answered=None):
        img = int(stream.take(1)[0])
        t = time.perf_counter()
        fut = svc.submit(img)
        k = w.add(img, t, t, fut)
        # the caller's next request was due at its last answer
        w.late.append(0.0 if answered is None
                      else (t - w.done[answered]) * 1e3)
        fut.add_done_callback(lambda f, k=k: finish(k, f))

    w.t0 = time.perf_counter()
    w.t1 = w.t0 + seconds
    for _ in range(int(callers)):
        send()
    while True:
        left = w.t1 - time.perf_counter()
        if left <= 0:
            break
        try:
            k = done.get(timeout=left)
        except queue.Empty:
            break
        send(k)
    drain(w)
    return w


def max_outstanding(w: Window) -> int:
    """The most requests of ``w`` sent and not yet answered at once (an
    answer at the instant of a send counts first)."""
    sent = np.asarray(w.sent)
    fin = np.asarray(w.done)
    fin = np.where(np.isnan(fin), np.inf, fin)
    t = np.concatenate([sent, fin])
    step = np.concatenate([np.ones(len(sent), int),
                           -np.ones(len(fin), int)])
    order = np.lexsort((step, t))
    return int(np.cumsum(step[order]).max()) if len(t) else 0


def fix_selector(b, selector_seed: int) -> None:
    """Serve (and check against) the actor drawn from ``selector_seed``
    in place of the run's seed-drawn one: the same shapes, so what
    ``build`` compiled serves it."""
    from bench import weights
    b.actor = weights.make_mlps(selector_seed, [b.actor_sizes])[0]
    b.agent.state = b.agent.state._replace(actor=b.actor)


def run(c: dict, seed: int, seconds: float, *, trace: bool, counter,
        t_start: float, control: bool = False) -> dict:
    traffic = c["traffic"]
    callers = int(traffic["callers"])
    if traffic["loop"] != "closed" or float(traffic["think_ms"]) != 0.0:
        raise ValueError("serve_closed drives a closed loop with no think "
                         f"time, got loop {traffic['loop']!r} think_ms "
                         f"{traffic['think_ms']!r}")
    b = build(c, seed, ids_needed(traffic, seconds), trace=trace)
    svc, core = b.svc, b.svc.core
    out = {"loop": traffic["loop"], "use_kernel": b.use_kernel,
           "iou_shapes": b.iou_shapes, "callers": callers}
    try:
        fix_selector(b, c["config"]["selector_seed"])
        warm = closed_loop(svc, b.stream, callers, traffic["warmup_s"])
        svc.reset_stats()
        if b.obs is not None:
            b.obs.metrics.reset()
        stats0 = dict(core.stats)
        b.spans.sums.clear()
        # every window starts from the same collector state
        gc.collect()
        t_setup = time.perf_counter()
        out["setup_s"] = t_setup - t_start
        if trace:
            import tempfile

            import jax
            prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            t_trace0 = time.perf_counter()
        w = closed_loop(svc, b.stream, callers, seconds)
        t_end = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            out["trace_dir"] = prof_dir
            out["trace_window"] = (0.0, t_end - t_trace0)
        out["compiles"] = counter.between(w.t0, t_end)
        out["setup_compiles"] = counter.between(t_start, t_setup)
        out["stats"] = dict(svc.stats)
        out["core_stats"] = {k: v - stats0.get(k, 0)
                             for k, v in core.stats.items()}
        out["obs"] = svc.metrics_snapshot() if b.obs is not None else None
        out["spans"] = dict(b.spans.sums)
    finally:
        svc.close()
    lat, results = outcomes(w)
    done = np.asarray(w.done)
    out.update({
        "attempted": len(w.ids),
        "failed": int(sum(r is None for r in results)),
        "latency_ms": lat,
        "late_ms": np.asarray(w.late),
        "providers_mean": float(np.mean(
            [np.sum(np.asarray(r.action) > 0.5) for r in results
             if r is not None])) if len(results) else 0.0,
        "completed_in_window": int(np.sum(done <= w.t1)),
        "window_s": w.t1 - w.t0,
        "windows": {"warmup": warm, "window": w},
        "max_outstanding": max(max_outstanding(warm), max_outstanding(w)),
        # tables built by the window: one per fresh image, each over its
        # unpadded box count
        "iou_box_counts": [int(b.box_counts[b.stream.alias[i]])
                           for i in set(w.ids)],
        "actor_sizes": b.actor_sizes,
    })
    out["memory_peak_bytes"] = common.memory_peak_bytes()
    from bench import weights
    params = weights.to_numpy(b.actor)
    n_check = c["limits"]["check_requests"]
    args = (b.env, b.traces, b.stream.alias, params, core, w, results,
            c["config"], n_check)
    out["checks"] = check(*args, np.random.default_rng(b.s_check))
    if control:
        import ml_dtypes
        out["control"] = check(*args, np.random.default_rng(b.s_check),
                               dt=ml_dtypes.bfloat16, control=True)
    return out


def summary(out: dict) -> str:
    return (f"{serve.summary(out)} callers={out['callers']} "
            f"completions_per_s="
            f"{out['completed_in_window'] / out['window_s']!r} "
            f"max_outstanding={out['max_outstanding']} "
            f"late_p99_ms={common.quantile(out['late_ms'], 0.99)!r} "
            f"providers_per_request={out['providers_mean']!r} "
            f"memory_peak_bytes={out['memory_peak_bytes']}")
