"""The program's host spans beside the benchmark's own: attached in a
traced run they count the same calls as the benchmark's wrappers, and the
trace reduction names the idle gaps they hold."""

import jax
import pytest

from harness import serving, trace
from test_bench_harness import CELLS, args, with_device_plane

PROGRAM = {"prefiller.request", "prefiller.prefill", "prefiller.stage",
           "decoder.request", "decoder.fill", "decoder.step",
           "decoder.sample"}


def test_program_spans_count_what_the_benchmark_counts(small_cell, monkeypatch):
    """The program's spans, attached to the traced run's recorder, count
    the prefills, decode steps and KV copies that ``Serving.instrument``
    wraps: the benchmark's span metrics could read them instead."""
    run = small_cell
    seen, names = [], []
    load, reduce = trace.load, trace.reduce
    instrument, uninstrument = serving.Serving.instrument, serving.Serving.uninstrument

    def attach(sut):
        instrument(sut)
        sut.fab.attach_spans(sut.rec)
        seen.append(sut)

    def detach(sut):
        uninstrument(sut)
        sut.fab.attach_spans(None)

    monkeypatch.setattr(serving.Serving, "instrument", attach)
    monkeypatch.setattr(serving.Serving, "uninstrument", detach)
    monkeypatch.setattr(trace, "load", lambda p: with_device_plane(load(p)))
    monkeypatch.setattr(trace, "reduce", lambda tr, n, spans=(), **kw: (
        names.append(set(spans)), reduce(tr, n, spans, **kw))[1])
    out = run.run(args(CELLS[0], 1), devices=jax.devices("cpu")[:1])
    assert out["correct"] is True, out["checks"]
    rec = seen[0].rec
    assert PROGRAM <= {name for name, *_ in rec.spans} and PROGRAM <= names[0]
    assert len(rec.named("decoder.step")) == len(rec.named("model.decode")) > 0
    seq = lambda name: sum(a["seq"] for *_, a in rec.named(name))
    assert seq("prefiller.prefill") == seq("model.prefill") > 0
    assert len(rec.named("prefiller.stage")) == len(rec.named("kv.stage"))
    assert len(rec.named("decoder.fill")) == len(rec.named("kv.fill"))
    # a request still in the handoff when the window closes is staged only
    assert rec.counters["kv.staged_bytes"] >= rec.counters["kv.filled_bytes"] > 0
    assert rec.counters["fabric.events"] > 0


def test_idle_gap_goes_to_the_innermost_program_span():
    host = [["bench.window", 0, 1000], ["fabric.loop", 0, 1000],
            ["decoder.request", 100, 600], ["decoder.step", 200, 200],
            ["model.decode", 250, 150], ["decoder.sample", 400, 100]]
    ops = [["%fusion.1 = f32[] fusion()", 0, 100],        # before the request
           ["%fusion.2 = f32[] fusion()", 150, 50],       # decoder.request self
           ["%fusion.3 = f32[] fusion()", 250, 150],      # the step's program
           ["%fusion.4 = f32[] fusion()", 500, 200],      # the sampling's end
           ["%fusion.5 = f32[] fusion()", 750, 250]]
    tr = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]}]}
    red = trace.reduce(tr, 1, {e[0] for e in host})
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == {"decoder.request": pytest.approx(50e-9),
                    "decoder.step": pytest.approx(50e-9),
                    "decoder.sample": pytest.approx(100e-9),
                    "fabric.loop": pytest.approx(50e-9)}
