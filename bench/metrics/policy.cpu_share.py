"""Share of the dispatcher's decision wall time that was its own CPU
time, %: the service's ``serving.policy_cpu_ms`` histogram (thread CPU
time of each ``policy`` phase) summed over the window, over the summed
``serving.policy_ms`` (the same phases' wall time; observability on,
traced run).  The rest is waiting: for the interpreter lock, or for the
device's answer."""


def read(out, device):
    hists = (out.get("obs") or {}).get("histograms") or {}
    cpu, wall = hists.get("serving.policy_cpu_ms"), hists.get(
        "serving.policy_ms")
    if not cpu or not wall or not cpu["count"] or wall["sum"] <= 0:
        return None
    return 100.0 * cpu["sum"] / wall["sum"]
