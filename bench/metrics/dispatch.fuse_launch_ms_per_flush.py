"""Wall time of the dispatcher's device-fuse launch per flush, ms: the
``fuse_launch`` phase (packing the flush's tables and rows, dispatching
the program, starting its copy to the host) from the service's
``serving.fuse_launch_ms`` histogram, summed over the window, over the
flushes (observability on, traced run).  Silent where the program has no
such phase or the phase never ran."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.fuse_launch_ms")
    n = (out.get("stats") or {}).get("flushes")
    if not h or not h["count"] or not n:
        return None
    return h["sum"] / n
