"""The prefill program's share of the chip's bf16 peak: the architecture's
required prefill FLOPs (``prefill_flops``) of the prompts prefilled inside
the window, over the device time of the ``prefill`` programs x chips x
peak.  None without a trace or without the count."""
from harness import trace


def read(data):
    a, red = data.sut.arch, data.red
    if red is None or not hasattr(a, "prefill_flops"):
        return None
    s, _ = trace.module_seconds(red, r"prefill")
    seqs = [x["seq"] for _, t0, t1, x in data.sut.rec.named("model.prefill")
            if data.sut.t0 <= t0 and t1 <= data.sut.t_close]
    if not s or not seqs:
        return None
    work = sum(a.prefill_flops(data.sut.dims, n) for n in seqs)
    return work / (s * data.chips * data.peak["bf16_flops_per_s"]) * 100.0
