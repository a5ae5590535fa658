"""Mean time a shard part of a flush waited between the dispatcher
handing it to its shard thread and that thread starting on it, ms (the
service's ``serving.shard_wait_ms`` histogram, observability on, traced
run): the shard threads' backlog, and their wait for the interpreter."""


def read(out, device):
    h = ((out.get("obs") or {}).get("histograms") or {}).get(
        "serving.shard_wait_ms")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
