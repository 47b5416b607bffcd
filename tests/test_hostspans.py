"""Host-clock spans in the serving program (``repro.obs.HostSpans``): off,
they change nothing and allocate nothing; on, every span names its request,
nests inside it, and the counters match what the fabric moved and ran."""

import itertools
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import EventLoop, Fabric
from repro.core.domain import MemoryRegion
from repro.ctrl import ControlPlane
from repro.models import init_params
from repro.obs import NULL_SPAN, HostSpans, host_count, host_span
from repro.serving import Decoder, Prefiller, Scheduler

PROMPTS = (20, 37)
N_DECODE = 4
PROGRAM_SPANS = {"prefiller.request", "prefiller.prefill", "prefiller.stage",
                 "decoder.request", "decoder.fill", "decoder.step",
                 "decoder.sample"}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("stablelm-3b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def serve(model, rec, monkeypatch):
    """Two requests through a prefiller and a decoder; region ids start
    from 0 so that two runs in one process put the same bytes on the
    wire."""
    cfg, params = model
    monkeypatch.setattr(MemoryRegion, "_ids", itertools.count())
    fab = Fabric(seed=3)
    if rec is not None:
        fab.attach_spans(rec)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=64)
    pf = Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl,
                   max_renewals=64)
    Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(0)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=n),
                         n_decode=N_DECODE) for n in PROMPTS]
    fab.run()
    done = {r: (sched.completed[r]["tokens"], sched.completed[r]["ttft_us"])
            for r in rids}
    return fab, pf, done


def test_spans_leave_tokens_and_virtual_times_unchanged(model, monkeypatch):
    fab_off, _, off = serve(model, None, monkeypatch)
    rec = HostSpans()
    fab_on, _, on = serve(model, rec, monkeypatch)
    assert on == off and fab_on.now == fab_off.now   # exact, not approx
    assert {name for name, *_ in rec.spans} == PROGRAM_SPANS
    assert fab_off.spans is None and fab_off.loop.spans is None
    assert host_span(fab_off, "decoder.step", rid=0, pos=1) is NULL_SPAN
    assert host_span(fab_off, "decoder.sample", rid=1) is NULL_SPAN


def test_spans_off_allocate_nothing():
    """Off, a span site returns the shared null context and keeps nothing;
    the same sites with a recorder attached keep a span each."""
    fab = Fabric()

    def sites(n):
        for i in range(n):
            with host_span(fab, "decoder.step", rid=i, pos=i + 1000):
                pass
            host_count(fab, "kv.filled_bytes", 4096)
        return tracemalloc.get_traced_memory()[1]

    sites(10)
    tracemalloc.start()
    try:
        off = sites(10_000)
        fab.attach_spans(HostSpans())
        tracemalloc.reset_peak()
        on = sites(10_000)
    finally:
        tracemalloc.stop()
    assert off < 4096, off
    assert on > 100 * 10_000
    assert len(fab.spans.spans) == 10_000
    assert fab.spans.counters["kv.filled_bytes"] == 4096 * 10_000


def test_every_span_names_its_request_and_nests_in_it(model, monkeypatch):
    rec = HostSpans()
    _, _, done = serve(model, rec, monkeypatch)
    assert all("rid" in attrs for *_, attrs in rec.spans)
    outer = {"prefiller": {}, "decoder": {}}
    for name, t0, t1, attrs in rec.spans:
        side, what = name.split(".")
        if what == "request":
            outer[side][attrs["rid"]] = (t0, t1, attrs)
    assert set(outer["prefiller"]) == set(outer["decoder"]) == set(done)
    for name, t0, t1, attrs in rec.spans:
        side, what = name.split(".")
        if what != "request":
            lo, hi, _ = outer[side][attrs["rid"]]
            assert lo <= t0 <= t1 <= hi, name
    for rid, (_, _, attrs) in outer["decoder"].items():
        steps = [a for n, *_, a in rec.spans
                 if n == "decoder.step" and a["rid"] == rid]
        assert len(steps) == attrs["steps"] == N_DECODE - 1
    assert sorted(a["seq"] for *_, a in rec.named("prefiller.prefill")) \
        == sorted(PROMPTS)


def test_counters_match_the_bytes_written_and_the_events_run(
        model, monkeypatch):
    ran = [0]

    def counted(fn):
        def run():
            ran[0] += 1
            fn()
        return run

    schedule, cancelable = EventLoop.schedule, EventLoop.schedule_cancelable
    monkeypatch.setattr(EventLoop, "schedule",
                        lambda self, d, fn: schedule(self, d, counted(fn)))
    monkeypatch.setattr(
        EventLoop, "schedule_cancelable",
        lambda self, d, fn: cancelable(self, d, counted(fn)))
    rec = HostSpans()
    _, pf, _ = serve(model, rec, monkeypatch)
    cfg = model[0]
    plan_bytes = sum(pf._plan(n).write_bytes for n in PROMPTS)
    tails = len(PROMPTS) * cfg.vocab * 4
    assert rec.counters["kv.staged_bytes"] == plan_bytes
    assert rec.counters["kv.filled_bytes"] == plan_bytes
    # what the prefiller's WRITEs carried: the KV pages and one tail each
    assert pf.engine.batch_stats.nbytes == plan_bytes + tails
    assert rec.counters["fabric.events"] == ran[0] > 0
