"""Arithmetic shared by the metric readers in ``bench/metrics/``."""

from __future__ import annotations

from typing import Optional

from . import trace


def per_request_ms(data, *names: str) -> Optional[float]:
    """Host span time of ``names`` per request prefilled in the window."""
    n = len(data.sut.rec.named("model.prefill"))
    if not n:
        return None
    return sum(data.sut.rec.total(x) for x in names) / n * 1e3


def idle_pct(data) -> Optional[float]:
    if data.red is None:
        return None
    return (1.0 - data.red["busy_s"] / data.red["window_s"]) * 100.0


def program_ms(data, pattern: str) -> Optional[float]:
    if data.red is None:
        return None
    s, calls = trace.module_seconds(data.red, pattern)
    return s / calls * 1e3 if calls else None


def serving_mfu(data) -> Optional[float]:
    """Required FLOPs of the prefills and decode steps run in the window
    over the window times the chip's peak."""
    if data.red is None:
        return None
    a, m, rec = data.sut.arch, data.sut.dims, data.sut.rec
    work = (sum(a.prefill_flops(m, s["seq"]) for *_, s in rec.named("model.prefill"))
            + sum(a.decode_flops(m, s["pos"]) for *_, s in rec.named("model.decode")))
    if not work:
        return None
    return work / (data.red["window_s"] * data.chips
                   * data.peak["bf16_flops_per_s"]) * 100.0
