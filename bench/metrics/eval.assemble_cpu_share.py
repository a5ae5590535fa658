"""Share of the shard threads' assembly wall time that was their own CPU
time, %: the service's ``serving.assemble_cpu_ms`` histogram (thread CPU
time of each ``shard_assemble`` phase) summed over the window, over the
summed durations of the ``shard_assemble`` spans (every request traced in
the traced run).  The rest is waiting: for the interpreter lock, or for
the device's answer."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.assemble_cpu_ms")
    s = (out.get("spans") or {}).get("shard_assemble")
    if not h or not s or s[0] <= 0:
        return None
    return 100.0 * h["sum"] / s[0]
