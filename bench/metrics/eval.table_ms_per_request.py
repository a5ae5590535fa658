"""Wall time of the shard threads' IoU table builds (merge, pad, kernel
launch, readback: the ``iou_table`` phase of ``shard_assemble``) per
request, ms: the service's ``serving.iou_table_ms`` histogram summed
over the window, over the requests (observability on, traced run)."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.iou_table_ms")
    n = (out.get("stats") or {}).get("requests")
    if not h or not n:
        return None
    return h["sum"] / n
