"""Disaggregated serving under a traffic mix: the path every serving cell
drives.

The topology is the program's own (``launch/serve.disaggregated``):
prefillers, decoders, a Scheduler and a ControlPlane on the simulated
fabric, in one process, sized by the configuration's ``deployment`` block.
Requests enter through ``Scheduler.submit``; the client holds a request's
first token when its completion record reaches the Scheduler.  The fabric's
event loop runs while work is outstanding and the next request is not yet
due, so arrivals follow the host's clock (open loop) or the replies
(closed loop).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from . import arch, model, traffic
from .spans import Recorder, patch

# lease renewals a peer may make in one run: far beyond the virtual time a
# window of requests spans, so no peer leaves the view while it is measured
MAX_RENEWALS = 10 ** 9


class Serving:
    def __init__(self, conf: Dict, mix: Dict, seed: int):
        self.conf, self.mix, self.seed = conf, mix, seed
        self.arch = arch.get(conf)
        self.cfg = self.arch.program_config(conf)
        self.dims = self.arch.dims(self.cfg)
        self.rec = Recorder()
        self.requests: List[Dict] = []

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        import jax
        self.params = jax.block_until_ready(
            model.make_params(self.arch, self.cfg,
                              model.seed32(self.seed, "weights")))
        model.check_layout(self.cfg, self.params)
        self._build()
        # every prompt length once, with the longest output: each length
        # compiles its prefill and decode programs here, not in the window
        warm = traffic.rng(self.seed, "warm-up")
        for n in traffic.prompt_lengths(self.mix):
            rid = self.sched.submit(
                warm.integers(0, self.cfg.vocab, n, dtype=np.int32),
                n_decode=traffic.max_output(self.mix))
            self.fab.run_until(lambda: rid in self.sched.completed)
            if rid not in self.sched.completed:
                raise RuntimeError(f"warm-up request of {n} tokens not served")
        self.done_seen = len(self.sched.completed)

    def _build(self) -> None:
        from repro.core import Fabric
        from repro.ctrl import ControlPlane
        from repro.serving import Decoder, Prefiller, Scheduler
        dep = self.conf["deployment"]
        pool = dict(max_seq_len=max(traffic.prompt_lengths(self.mix)),
                    max_inflight=self.mix["pool_requests"])
        self.fab = Fabric(seed=1)
        ctrl = ControlPlane(self.fab, nic=dep["nic"])
        self.peers = []
        for i in range(dep["prefillers"]):
            self.peers.append(Prefiller(
                self.fab, f"p{i}", self.cfg, self.params, nic=dep["nic"],
                ctrl=ctrl, max_renewals=MAX_RENEWALS, **pool))
        for i in range(dep["decoders"]):
            self.peers.append(Decoder(
                self.fab, f"d{i}", self.cfg, self.params, nic=dep["nic"],
                ctrl=ctrl, max_renewals=MAX_RENEWALS, **pool))
        self.sched = Scheduler(self.fab, ctrl)

    # -- the measured window --------------------------------------------------
    def _submit(self, req: traffic.Request, due: float) -> None:
        now = time.perf_counter()
        rid = self.sched.submit(req.prompt, n_decode=req.n_out)
        self.requests.append({"rid": rid, "prompt": req.prompt,
                              "n_out": req.n_out, "due": due, "sent": now,
                              "done": None, "tokens": None})

    def _outstanding(self) -> bool:
        return bool(self.sched.backlog or self.sched.inflight)

    def window(self, seconds: float) -> None:
        gen = traffic.requests(self.mix, self.seed, self.cfg.vocab)
        by_rid: Dict[int, Dict] = {}
        closed = self.mix["kind"] == "closed_loop"
        t0 = time.perf_counter()
        self.t0, self.t_end = t0, t0 + seconds
        nxt: Optional[traffic.Request] = None
        if closed:
            for _ in range(self.mix["clients"]):
                self._submit(next(gen), t0)
        else:
            nxt = next(gen)
        by_rid.update({r["rid"]: r for r in self.requests})

        def stamp() -> None:
            done = self.sched.completed
            if len(done) == self.done_seen:
                return
            now = time.perf_counter()
            for rid in list(done)[self.done_seen:]:
                r = by_rid.get(rid)
                if r is not None:
                    r["done"], r["tokens"] = now, done[rid]["tokens"]
                    if closed and now < self.t_end:
                        self._submit(next(gen), now)
                        by_rid[self.requests[-1]["rid"]] = self.requests[-1]
            self.done_seen = len(done)

        while True:
            now = time.perf_counter()
            if now >= self.t_end:
                break
            while nxt is not None and t0 + nxt.due_s <= now:
                self._submit(nxt, t0 + nxt.due_s)
                by_rid[self.requests[-1]["rid"]] = self.requests[-1]
                nxt = next(gen)
            until = min(self.t_end, t0 + nxt.due_s) if nxt else self.t_end
            if self._outstanding():
                with self.rec.span("fabric.loop"):
                    self.fab.run_until(lambda: (
                        stamp(), time.perf_counter() >= until
                        or not self._outstanding())[1])
                stamp()
            else:
                time.sleep(max(0.0, until - time.perf_counter()))
        self.t_close = time.perf_counter()
        self.backlog_end = len(self.sched.backlog) + len(self.sched.inflight)
        self.failed = len(self.sched.failed)

    # -- spans around the program's layers (traced runs only) ---------------
    def instrument(self) -> None:
        import jax
        from repro.serving import disagg
        rec = self.rec

        def prefill(orig):
            def run(params, tokens, cfg, **kw):
                with rec.span("model.prefill", seq=int(tokens.shape[1])):
                    return jax.block_until_ready(orig(params, tokens, cfg, **kw))
            return run

        def decode(orig):
            def run(params, tokens, positions, cache, cfg, **kw):
                with rec.span("model.decode", pos=int(np.asarray(positions)[0])):
                    return jax.block_until_ready(
                        orig(params, tokens, positions, cache, cfg, **kw))
            return run

        def staged(name):
            def wrap(orig):
                def run(*a, **kw):
                    with rec.span(name):
                        return orig(*a, **kw)
                return run
            return wrap

        self.undo = [patch(disagg, "prefill_jit", prefill),
                     patch(disagg, "decode_step_jit", decode),
                     patch(disagg, "stage_cache", staged("kv.stage")),
                     patch(disagg.Decoder, "_assemble_cache", staged("kv.fill"))]

    def uninstrument(self) -> None:
        for undo in getattr(self, "undo", []):
            undo()

    # -- after the window -----------------------------------------------------
    def in_window(self) -> List[Dict]:
        return [r for r in self.requests if r["due"] < self.t_end]

    def completed(self) -> List[Dict]:
        return [r for r in self.in_window()
                if r["done"] is not None and r["done"] <= self.t_close]

    def counters(self) -> Dict:
        sent = self.in_window()
        late = sorted(r["sent"] - r["due"] for r in sent)
        return {"attempted": len(sent), "completed": len(self.completed()),
                "failed": self.failed, "backlog_at_close": self.backlog_end,
                "lateness_p50_s": late[len(late) // 2] if late else 0.0,
                "lateness_max_s": late[-1] if late else 0.0}

    def free(self) -> None:
        """Drop the serving state; the weights stay for the reference."""
        self.peers = self.sched = self.fab = None

    def served_sample(self, seed: int, target: int) -> List[Dict]:
        """Finished requests drawn from the seed until ``target`` served
        tokens are in the sample; the longest prompt is always in it."""
        done = self.completed()
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r["prompt"]), r["n_out"]))
        rest = [r for r in done if r is not longest]
        order = traffic.rng(seed, "sample").permutation(len(rest))
        out, n = [longest], len(longest["tokens"])
        for i in order:
            if n >= target:
                break
            out.append(rest[i])
            n += len(rest[i]["tokens"])
        return out

    # the numbers a limits file may hold, over the sample's served tokens
    # (one array of gaps per sampled request)
    GAP_STATS = {
        "logit_gap": lambda g: np.max(np.concatenate(g)),
        "mean_logit_gap": lambda g: np.mean(np.concatenate(g)),
        "request_mean_gap": lambda g: max(np.mean(x) for x in g),
    }

    def gaps(self, seed: int, limits: Dict,
             control: bool = False) -> List[np.ndarray]:
        """For each sampled finished request, how far each served token's
        reference logit lies below the reference's best (``control``: the
        float8 control's first choice in its place)."""
        from . import reference
        sample = self.served_sample(seed, limits["sample_tokens"])
        if not sample:
            return []
        gaps = reference.served_gaps(
            self.arch.forward, self.params, self.dims,
            [(r["prompt"], r["tokens"]) for r in sample],
            traffic.pad_to(self.mix), control=control)
        print(f"checked {sum(g.size for g in gaps)} served tokens of "
              f"{len(sample)} requests", flush=True)
        return gaps

    def check(self, seed: int, limits: Dict) -> Dict[str, Dict]:
        """The limits file's statistics of the served tokens' logit gaps;
        nothing finished reads as infinitely far off."""
        gaps = self.gaps(seed, limits)
        return {name: {"value": float(fn(gaps)) if gaps else float("inf"),
                       "limit": limits[name]}
                for name, fn in self.GAP_STATS.items() if name in limits}
