"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

``open_loop``
    ``rate_per_s`` arrivals on a Poisson schedule, ``prompt_len`` (length ->
    share), ``output_len`` (``{"uniform": [lo, hi]}`` or ``{"fixed": n}``)
    and ``deck`` (requests per deck).
``closed_loop``
    ``clients`` that each send their next request when the last one is
    answered, with the same length parameters.

Lengths and inter-arrival gaps are dealt from decks shuffled by the run's
seed: every deck holds the stated mix exactly (the gaps are the conditional
means of the exponential distribution over equal-probability strata, so
their mean is exactly ``1 / rate``).  Every seed thus sends the same set of
sizes and arrivals, in its own order, with its own token ids.
"""

from __future__ import annotations

import json
import math
import pathlib
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

KINDS = ("open_loop", "closed_loop")


def load(path: pathlib.Path) -> Dict:
    mix = json.loads(pathlib.Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    return mix


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of a run's seed (any size)."""
    return np.random.default_rng([seed, *stream.encode()])


def length_deck(shares: Dict[str, float], deck: int) -> List[int]:
    """One deck of ``deck`` lengths holding ``shares`` exactly."""
    counts = {int(k): v * deck for k, v in shares.items()}
    if any(abs(c - round(c)) > 1e-9 for c in counts.values()):
        raise ValueError(f"shares {shares} do not fill a deck of {deck}")
    out = [k for k, c in sorted(counts.items()) for _ in range(round(c))]
    if len(out) != deck:
        raise ValueError(f"shares {shares} sum to {len(out)}, not {deck}")
    return out


def output_deck(spec: Dict) -> List[int]:
    if "fixed" in spec:
        return [int(spec["fixed"])]
    lo, hi = spec["uniform"]
    return list(range(int(lo), int(hi) + 1))


def exp_strata(n: int) -> List[float]:
    """Means of Exp(1) over ``n`` strata of equal probability."""
    def primitive(v: float) -> float:        # integral of -ln(v) dv
        return v - v * math.log(v) if v > 0 else 0.0
    return [n * (primitive(1 - i / n) - primitive(1 - (i + 1) / n))
            for i in range(n)]


def dealt(r: np.random.Generator, deck: List) -> Iterator:
    """Endless stream of shuffled copies of ``deck``."""
    while True:
        for i in r.permutation(len(deck)):
            yield deck[i]


@dataclass
class Request:
    index: int
    due_s: float            # offset from the window's start (open loop)
    prompt: np.ndarray      # int32 token ids
    n_out: int


def requests(mix: Dict, seed: int, vocab: int) -> Iterator[Request]:
    """The mix's requests in sending order (gaps only for open loop)."""
    lens = dealt(rng(seed, "prompt_len"),
                 length_deck(mix["prompt_len"], mix["deck"]))
    outs = dealt(rng(seed, "output_len"), output_deck(mix["output_len"]))
    ids = rng(seed, "tokens")
    gaps = (dealt(rng(seed, "gaps"), exp_strata(mix["deck"]))
            if mix["kind"] == "open_loop" else None)
    t, i = 0.0, 0
    while True:
        if gaps is not None:
            t += next(gaps) / mix["rate_per_s"]
        n = next(lens)
        yield Request(i, t, ids.integers(0, vocab, n, dtype=np.int32),
                      next(outs))
        i += 1


def prompt_lengths(mix: Dict) -> List[int]:
    return sorted(int(k) for k in mix["prompt_len"])


def max_output(mix: Dict) -> int:
    return max(output_deck(mix["output_len"]))


def pad_to(mix: Dict, multiple: int = 128) -> int:
    """The longest prompt with the longest output, rounded up: one length
    that every request of the mix fits."""
    n = max(prompt_lengths(mix)) + max_output(mix)
    return -(-n // multiple) * multiple
