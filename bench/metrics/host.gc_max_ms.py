"""Longest garbage collection inside the traced window, ms: the longest
``gc.gen<k>`` host event (the program's annotation of each collection,
on the profiler's clock), clipped to the window; 0.0 where none ran.
Silent where the program does not time its collections (no
``runtime.gc_ms.*`` histogram in its registry)."""
from bench import trace as tr


def read(out, device):
    t = out.get("trace")
    hists = (out.get("obs") or {}).get("histograms") or {}
    if t is None or not any(k.startswith("runtime.gc_ms.") for k in hists):
        return None
    gcs = tr.clip([(name, a, b) for _, name, a, b in t.host
                   if name.startswith("gc.")], t.window)
    return max((b - a for _, a, b in gcs), default=0.0) * 1e3
