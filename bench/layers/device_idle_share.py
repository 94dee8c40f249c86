"""``device_idle_share`` (layer: device): 1 - the union of the op-level
device events in the traced window over the window, averaged over the
chips, in %."""
from bench import trace as tr


def read(record: dict):
    t = record.get("trace")
    if t is None:
        return None
    busy = tr.busy_ns(t)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / tr.window_ns(t))
