"""The step's share of its HBM roofline: the batch's necessary bytes
(``bench/roofline.py``, from shapes alone) over the chip's HBM bandwidth
times the device busy time per batch (device trace).  Bound by bytes: the
step's arithmetic is a few integer compares per byte."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    per_batch = r.trace.busy_s / r.window.batches
    return 100.0 * r.bytes_per_batch / (r.peak.hbm_bytes_per_s * per_batch)
