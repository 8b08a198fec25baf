"""The Pallas bucket combine's share of its roofline: the bytes its
launches in the traced window must move (each reads the accumulator and
the incoming round and writes the result), at the chip's HBM
bandwidth, over the device time of those launches."""
from bench import flops


def read(m):
    layout = m.facts.get("bucket_combine")
    op = m.trace["sync"]["ops"]["bucket_combine"] if m.trace else None
    if not layout or not op or not op["count"] or not op["ns"]:
        return None
    nbytes = op["count"] * flops.bucket_combine_bytes(
        layout["rows"], layout["bucket_elems"])
    return 100.0 * nbytes / m.peaks["hbm_bytes_per_s"] / (op["ns"] / 1e9)
