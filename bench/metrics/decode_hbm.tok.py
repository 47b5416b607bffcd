"""The decode step's share of the chip's HBM bandwidth: the bytes each
step inside the window must read (the architecture's ``decode_bytes``:
weights the token uses, the head and the cache rows up to its position),
over the device time of the ``decode_step`` programs x chips x peak.
None without a trace or without the count."""
from harness import trace


def read(data):
    a, red = data.sut.arch, data.red
    if red is None or not hasattr(a, "decode_bytes"):
        return None
    s, _ = trace.module_seconds(red, r"decode_step")
    pos = [x["pos"] for _, t0, t1, x in data.sut.rec.named("model.decode")
           if data.sut.t0 <= t0 and t1 <= data.sut.t_close]
    if not s or not pos:
        return None
    moved = sum(a.decode_bytes(data.sut.dims, p) for p in pos)
    return moved / (s * data.chips * data.peak["hbm_bytes_per_s"]) * 100.0
