"""Share of the window's requests whose ensemble came from their flush's
device fuse (grouping, voting and WBF in one device program the
dispatcher launches), %: the service's ``stats["fused_on_device"]`` over
``stats["requests"]``.  Silent where the program has no such counter or
served no request."""


def read(out, device):
    st = out.get("stats") or {}
    if not st.get("requests") or "fused_on_device" not in st:
        return None
    return 100.0 * st["fused_on_device"] / st["requests"]
