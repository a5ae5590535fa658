"""Device time of the device fuse (the ``fuse_flush`` program: grouping,
voting and WBF of a flush's cold ensembles), per execution,
microseconds, from the trace.  Silent where the program never ran."""
from bench import trace as tr


def read(out, device):
    t = out.get("trace")
    if t is None:
        return None
    secs, n = tr.time_of(t.modules, lambda m: m.startswith("jit_fuse_flush"),
                         t.window)
    return secs / n * 1e6 if n else None
