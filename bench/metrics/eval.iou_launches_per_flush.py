"""IoU kernel launches per flush of the service over the window: its
``stats["iou_launches"]`` (the dispatcher's one launch for a flush's
untabled images, and any a shard thread made for images that launch
lacked) over ``stats["flushes"]``.  Silent where the program has no
such counter or served no flush."""


def read(out, device):
    st = out.get("stats") or {}
    if not st.get("flushes") or "iou_launches" not in st:
        return None
    return st["iou_launches"] / st["flushes"]
