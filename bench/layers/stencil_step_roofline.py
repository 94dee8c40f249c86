"""``stencil_step_roofline`` (layer: kernels): the least HBM bytes of the
window's work (``bench/work.py``) at the chip's peak bandwidth, over the
device busy time in the window, in %.  Bound: HBM bandwidth; no float32
peak of the chip's vector units is published, so no FLOP bound is
taken.  Per chip: the bytes are split evenly over the chips and the busy
time is their mean."""
from bench import peaks
from bench import trace as tr


def read(record: dict):
    t = record.get("trace")
    if t is None:
        return None
    busy = tr.busy_ns(t)
    busy_s = sum(busy.values()) / len(busy) / 1e9
    bw = peaks.peaks(record["device_kind"])["hbm_bytes_per_s"]
    least_s = record["least_bytes"] / record["chips"] / bw
    return 100.0 * least_s / busy_s
