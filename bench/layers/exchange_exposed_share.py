"""``exchange_exposed_share`` (layer: exchange): per chip, the time of
its collective ops (by XLA op kind) during which no other op runs on it,
over the traced window; the worst chip, in %.  Nothing where the trace
holds no collective."""
from bench import trace as tr


def read(record: dict):
    t = record.get("trace")
    if t is None:
        return None
    exposed = tr.exposed_ns(t)
    if not exposed:
        return None
    return 100.0 * max(exposed.values()) / tr.window_ns(t)
