"""Wall time of the dispatcher's IoU launch per flush, ms: the
``iou_launch`` phase (the launch issued ahead of the decision and its
readback after it) from the service's ``serving.iou_launch_ms``
histogram, summed over the window, over the flushes (observability on,
traced run).  Silent where the program has no such phase or the phase
never ran."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.iou_launch_ms")
    n = (out.get("stats") or {}).get("flushes")
    if not h or not h["count"] or not n:
        return None
    return h["sum"] / n
