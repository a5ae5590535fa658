"""Share of the device's long idle time that garbage collection covers,
%: every stretch of the window with no device operation, formed as
``bench.trace.idle_gaps`` forms them, that lasts 50 ms or more, and the
part of those stretches inside the union of the ``gc.gen<k>`` host events
(the program's annotation of each collection); 0.0 where no gap reaches
50 ms.  Silent where the program does not time its collections (no
``runtime.gc_ms.*`` histogram in its registry)."""
from bench import trace as tr

LONG_S = 0.05


def gaps(t) -> list:
    """Every stretch of the window with no device operation."""
    lo, hi = t.window
    out, at = [], lo
    for a, b in tr.union([(a, b) for _, a, b in tr.clip(t.ops, t.window)]):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def covered(spans: list, cover: list) -> float:
    """Seconds of the disjoint, sorted ``spans`` inside the disjoint,
    sorted ``cover``."""
    total, j = 0.0, 0
    for a, b in spans:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def read(out, device):
    t = out.get("trace")
    hists = (out.get("obs") or {}).get("histograms") or {}
    if t is None or not any(k.startswith("runtime.gc_ms.") for k in hists):
        return None
    long = [(a, b) for a, b in gaps(t) if b - a >= LONG_S]
    if not long:
        return 0.0
    gcs = tr.union([(a, b) for _, a, b in tr.clip(
        [(name, a, b) for _, name, a, b in t.host
         if name.startswith("gc.")], t.window)])
    return 100.0 * covered(long, gcs) / sum(b - a for a, b in long)
