"""Orchestration + oracle for the host-proxy MoE kernels."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core import Fabric
from .dispatch import MoEConfig, MoEEndpoint


def make_endpoints(fabric: Fabric, cfg: MoEConfig, *, nic: str = "cx7",
                   gpus_per_node: int = 8, nvlink: bool = False,
                   nics: Optional[List[str]] = None) -> List[MoEEndpoint]:
    """One engine per EP rank, grouped ``gpus_per_node`` ranks to a node.

    ``nvlink=True`` registers ranks of one node under a shared physical
    host, so same-node dispatch/combine payloads ride the NVLink fast path
    (paper §6) while cross-node traffic keeps the NIC.  ``nics`` optionally
    gives a per-rank NIC preset list (Holmes-style mixed clusters); it
    overrides ``nic``.  The default (``nvlink=False``, uniform ``nic``) is
    bit-identical to the pre-heterogeneous-fabric behaviour."""
    eps = []
    for r in range(cfg.n_ranks):
        node = f"node{r // gpus_per_node}"
        rank_nic = nics[r] if nics is not None else nic
        eng = fabric.add_engine(f"{node}-r{r}", nic=rank_nic,
                                host=node if nvlink else None,
                                nvlink=nvlink)
        eps.append(MoEEndpoint(fabric, cfg, r, eng))
    # endpoints exchange ONLY serializable ports (rank + MrDescs): all
    # placement offsets must be derived from the routes on the wire
    ports = [e.port() for e in eps]
    for e in eps:
        e.connect(ports)
    return eps


def run_moe_layer(fabric: Fabric, eps: List[MoEEndpoint],
                  tokens: List[np.ndarray], eids: List[np.ndarray],
                  gates: List[np.ndarray],
                  expert_fn: Callable[[int, np.ndarray], np.ndarray],
                  dtype=np.float32) -> Tuple[List[np.ndarray], Dict]:
    """One dispatch -> expert -> combine round across all ranks.

    tokens[r]: (T, elems) dtype; eids[r]: (T, top_k); gates[r]: (T, E) dense.
    expert_fn(global_expert_id, slab (n, elems)) -> (n, elems).
    Returns (combined outputs per rank, stats).
    """
    from ..obs import traced_window

    cfg = eps[0].cfg
    N = cfg.n_ranks
    ctxs: List[Dict] = [None] * N
    done = {"disp": 0, "comb": 0}

    def start_combine(r: int) -> None:
        ep = eps[r]
        slabs = ep.gather_expert_tokens(ctxs[r])
        outs = []
        elems = cfg.token_bytes // np.dtype(dtype).itemsize
        for e_loc, slab in enumerate(slabs):
            e = r * cfg.e_local + e_loc
            x = slab.view(dtype).reshape(slab.shape[0], elems)
            y = expert_fn(e, x).astype(dtype)
            outs.append(y.view(np.uint8).reshape(y.shape[0], cfg.token_bytes))
        ep.combine(ctxs[r], outs,
                   lambda: done.__setitem__("comb", done["comb"] + 1))

    with traced_window(fabric, "moe.layer"):
        for r, ep in enumerate(eps):
            tok_bytes = tokens[r].astype(dtype).view(np.uint8).reshape(
                tokens[r].shape[0], -1)
            ctxs[r] = ep.dispatch(tok_bytes, eids[r],
                                  lambda r=r: (done.__setitem__("disp", done["disp"] + 1),
                                               start_combine(r)))
        fabric.run()
    if fabric.tracer is not None:
        fabric.tracer.sample_gauges()
    assert done["disp"] == N and done["comb"] == N, (done, N)

    results = [eps[r].combine_result(ctxs[r], gates[r], dtype=dtype)
               for r in range(N)]
    stats = {
        "dispatch_us": [e.stats.get("dispatch_us", 0.0) for e in eps],
        "combine_us": [e.stats.get("combine_us", 0.0) for e in eps],
    }
    return results, stats


def oracle(tokens: List[np.ndarray], eids: List[np.ndarray],
           gates: List[np.ndarray], expert_fn, n_experts: int
           ) -> List[np.ndarray]:
    """Dense reference: y[t] = sum_e gates[t,e] * f_e(x[t])."""
    out = []
    for r in range(len(tokens)):
        x = tokens[r].astype(np.float32)
        y = np.zeros_like(x)
        for e in range(n_experts):
            w = gates[r][:, e:e + 1]
            if (w != 0).any():
                y += w * expert_fn(e, x)
        out.append(y)
    return out
