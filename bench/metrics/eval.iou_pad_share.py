"""Share of the IoU entries the shard launches computed that no table
uses, %: 1 - used / launched, from the service's
``serving.iou_entries_used`` (the sum of n^2 over the tables built) and
``serving.iou_entries_launched`` (padded images x boxes^2 of each launch)
counters over the window (thread plane, observability on, traced run).
Silent where the program has no such counters or launched nothing."""


def read(out, device):
    c = (out.get("obs") or {}).get("counters") or {}
    launched = c.get("serving.iou_entries_launched")
    used = c.get("serving.iou_entries_used")
    if not launched or used is None:
        return None
    return 100.0 * (1.0 - used / launched)
