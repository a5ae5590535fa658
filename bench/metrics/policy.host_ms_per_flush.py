"""Host wall time of the dispatcher's decision per flush, ms: feature
gather, padding, the policy forward and its actions back on the host
(the service's ``serving.policy_ms`` histogram, observability on, traced
run).  Beside ``policy.device_us_per_flush`` it says how much of the
decision is host time."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.policy_ms")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
