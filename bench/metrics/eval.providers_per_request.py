"""Providers each request's action selected, mean over the window: the
service's ``serving.providers_selected`` histogram (observability on,
traced run).  Each selected provider adds its detections to the fused
table and its fee to the bill.  Silent where the program has no such
histogram or it counted nothing."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.providers_selected")
    if not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]
