"""The traffic generator: deterministic per seed, decks that hold the
stated mix, an open-loop schedule at the stated rate."""

import itertools
import json
import pathlib

import numpy as np
import pytest

from harness import traffic

MIXES = pathlib.Path(__file__).resolve().parents[2] / "bench" / "traffic"
BIG_SEED = 2 ** 31 + 12345


# an open-loop mix as a data file would hold it
OPEN_LOOP = {"kind": "open_loop", "rate_per_s": 0.5, "deck": 10,
             "prompt_len": {"128": 0.4, "256": 0.3, "512": 0.2, "1024": 0.1},
             "output_len": {"uniform": [16, 32]}}


def mix_named(name):
    if name == "open-loop":
        return dict(OPEN_LOOP)
    return traffic.load(MIXES / f"{name}.json")


def take(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.requests(mix, seed, vocab), n))


@pytest.mark.parametrize("name", ["open-loop", "decode-batch"])
def test_same_seed_same_requests(name):
    mix = mix_named(name)
    a, b = take(mix, BIG_SEED, 30), take(mix, BIG_SEED, 30)
    assert [(r.due_s, r.n_out, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.n_out, r.prompt.tolist()) for r in b]
    c = take(mix, BIG_SEED + 1, 30)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("name", ["open-loop", "decode-batch"])
def test_decks_hold_the_mix(name):
    mix = mix_named(name)
    deck = mix["deck"]
    for seed in (1, BIG_SEED):
        reqs = take(mix, seed, deck * 7)
        for d in range(7):
            lens = [len(r.prompt) for r in reqs[d * deck:(d + 1) * deck]]
            for length, share in mix["prompt_len"].items():
                assert lens.count(int(length)) == round(share * deck)
        outs = [r.n_out for r in reqs]
        lo, hi = min(traffic.output_deck(mix["output_len"])), \
            max(traffic.output_deck(mix["output_len"]))
        assert lo <= min(outs) and max(outs) <= hi
        assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
                   for r in reqs)


def test_open_loop_rate():
    mix = mix_named("open-loop")
    deck = mix["deck"]
    reqs = take(mix, BIG_SEED, deck * 20)
    # every deck of gaps sums to deck / rate exactly
    for d in range(1, 21):
        assert reqs[d * deck - 1].due_s == pytest.approx(d * deck / mix["rate_per_s"])
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    assert (gaps > 0).all()
    # a Poisson schedule: gaps spread like an exponential's
    assert np.std(gaps) * mix["rate_per_s"] == pytest.approx(1.0, abs=0.15)


def test_exp_strata_mean_is_one():
    for n in (1, 4, 10, 17):
        s = traffic.exp_strata(n)
        assert np.mean(s) == pytest.approx(1.0)
        assert s == sorted(s)


def test_closed_loop_has_no_schedule():
    mix = traffic.load(MIXES / "decode-batch.json")
    assert all(r.due_s == 0.0 for r in take(mix, 3, 20))
    assert {r.n_out for r in take(mix, 3, 20)} == {64}


def test_bad_mix_is_refused(tmp_path):
    with pytest.raises(ValueError):
        traffic.length_deck({"128": 0.45, "256": 0.55}, 10)
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"kind": "bursty"}))
    with pytest.raises(ValueError):
        traffic.load(p)


@pytest.mark.parametrize("name", ["open-loop", "decode-batch"])
def test_every_seed_sends_the_same_sizes_in_its_own_order(name):
    mix = mix_named(name)
    n = 4 * mix["deck"]
    a, b = take(mix, 1, n), take(mix, BIG_SEED, n)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    if mix["kind"] == "open_loop":
        # the same arrivals in all: every deck of gaps sums alike
        assert a[-1].due_s == pytest.approx(b[-1].due_s)
        assert [r.due_s for r in a] != [r.due_s for r in b]


def test_pad_to_fits_every_request():
    mix = traffic.load(MIXES / "decode-batch.json")
    assert traffic.pad_to(mix) == 384                  # 256 + 64, rounded up
    assert traffic.pad_to(mix_named("open-loop")) == 1152
    assert traffic.pad_to(dict(mix, output_len={"fixed": 128})) == 384
