"""TransferPlan: compile a KvSchema into batched scatter groups.

The compiler runs once per (schema, seq_len) — *before* any request touches
the hot path — and emits:

* a **canonical slot order** for the handoff: components in schema order,
  stack entries in layer order, pages ("chunks") in token order.  Both ends
  allocate pool pages in this order, so a flat page-id list in the
  DispatchReq fully describes the destination page table;
* a **trigger index**: for every model layer, the (component, slot) writes
  that become transferable when that layer's compute completes — this is
  what the Prefiller's UvmWatcher spans consume;
* an **ImmCounter expectation map**: one immediate per component
  (``base_imm + component_index``) with its total WRITE count, so the
  receiver can arm all counters before the first byte lands.

The hot path then degenerates to :meth:`TransferPlan.submit_span`: ONE
``submit_scatters`` call — one ``WrBatch``, one event-loop enqueue — per
completed layer span, regardless of how many components/pages the span
covers (§3.4 WR templating; arXiv 2605.00686 plan-ahead).

``stage_cache`` / ``fill_cache`` bridge the model's cache pytree and pool
slots on the two ends; they are byte-exact inverses over the valid extent
of every component.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import ScatterDst
from .schema import KvSchema, handoff_max_len


class TransferPlan:
    """Precompiled scatter layout for one (schema, seq_len)."""

    def __init__(self, schema: KvSchema, seq_len: int):
        self.schema = schema
        self.seq_len = seq_len
        self.max_len = handoff_max_len(seq_len)
        self.slot_bytes = schema.slot_bytes
        pt = schema.page_tokens
        # writes that unlock when model layer t completes: (comp_idx, slot)
        self.by_trigger: List[List[Tuple[int, int]]] = \
            [[] for _ in range(schema.n_layers)]
        self.comp_chunks: List[int] = []    # pages per stack layer, per comp
        self.comp_page_len: List[int] = []  # WRITE length, per comp
        self._slots: Dict[Tuple[int, int, int], int] = {}
        n = 0
        for ci, comp in enumerate(schema.components):
            chunks = comp.chunks(seq_len, self.max_len, pt)
            self.comp_chunks.append(chunks)
            self.comp_page_len.append(comp.page_len(pt))
            for s in range(comp.n_stack):
                trig = comp.layers[s]
                for c in range(chunks):
                    self._slots[(ci, s, c)] = n
                    self.by_trigger[trig].append((ci, n))
                    n += 1
        self.n_slots = n          # pool pages per side, canonical order
        self.total_writes = n     # one WRITE per page
        # bytes the handoff's WRITEs carry (one page length per slot)
        self.write_bytes = sum(
            self.comp_page_len[ci] * comp.n_stack * self.comp_chunks[ci]
            for ci, comp in enumerate(schema.components))

    # -- introspection -------------------------------------------------------
    @property
    def n_imms(self) -> int:
        """Distinct immediates used (one per component; callers may claim
        one more for the tail write)."""
        return len(self.schema.components)

    def slot(self, comp_idx: int, stack: int, chunk: int) -> int:
        return self._slots[(comp_idx, stack, chunk)]

    def expected_counts(self) -> List[Tuple[int, int]]:
        """Receiver expectation map: (imm offset, WRITE count) per
        component.  Arm each as ``expect_imm_count(base_imm + off, count)``."""
        return [(ci, comp.n_stack * self.comp_chunks[ci])
                for ci, comp in enumerate(self.schema.components)
                if comp.n_stack * self.comp_chunks[ci] > 0]

    def span_writes(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        """(comp_idx, slot) writes unlocked by model layers [lo, hi)."""
        out: List[Tuple[int, int]] = []
        for t in range(lo, hi):
            out.extend(self.by_trigger[t])
        return out

    # -- hot path ------------------------------------------------------------
    def submit_span(self, engine, src_handle, src_pages: Sequence[int],
                    dst_desc, dst_pages: Sequence[int], base_imm: int,
                    lo: int, hi: int,
                    on_sent: Optional[Callable[[int], None]] = None,
                    on_error: Optional[Callable[[str], None]] = None,
                    fence_epoch: Optional[int] = None) -> int:
        """WRITE everything unlocked by layers [lo, hi): ONE WrBatch.

        ``src_pages``/``dst_pages`` are the two pools' page ids in canonical
        slot order.  Each component rides its own immediate
        (``base_imm + comp_idx``); ``on_sent(n)`` fires once per component
        group with its write count when that group has sender completions.
        ``on_error(reason)`` (fault injection) fires when a component
        group's WRITEs exhaust their retry budget — at most once per group;
        the caller dedups across groups.  ``fence_epoch`` stamps every
        WRITE with the sender's view epoch for the receiver's epoch fence
        (zombie-writer guard); None posts unstamped.  Returns the number of
        WRITEs templated."""
        stride = self.slot_bytes
        per_comp: Dict[int, List[ScatterDst]] = {}
        for ci, slot in self.span_writes(lo, hi):
            per_comp.setdefault(ci, []).append(ScatterDst(
                len=self.comp_page_len[ci],
                src=src_pages[slot] * stride,
                dst=(dst_desc, dst_pages[slot] * stride)))
        if not per_comp:
            return 0
        groups = []
        for ci in sorted(per_comp):
            dsts = per_comp[ci]
            cb = ((lambda n=len(dsts): on_sent(n))
                  if on_sent is not None else None)
            groups.append((src_handle, dsts, base_imm + ci, cb, on_error,
                           fence_epoch))
        engine.submit_scatters(groups)
        return sum(len(d) for d in per_comp.values())


def compile_plan(src_schema: KvSchema, dst_schema: KvSchema,
                 seq_len: int) -> TransferPlan:
    """Validate src/dst compatibility and compile the plan.

    Programmatic entry point for hand-wired setups and tests.  The serving
    stack performs the same ``KvSchema.mismatch`` check twice on its own:
    the Scheduler refuses mismatched pairings at routing time, and the
    Prefiller re-validates the schema carried in each ``DispatchReq``
    before the first WRITE."""
    reason = src_schema.mismatch(dst_schema)
    if reason is not None:
        raise ValueError(f"incompatible KvSchemas: {reason}")
    return TransferPlan(src_schema, seq_len)


# ---------------------------------------------------------------------------
# cache <-> pool staging (both directions are schema-generic)
# ---------------------------------------------------------------------------

def _comp_np(cache: Dict[str, object], comp) -> np.ndarray:
    arr = np.asarray(cache[comp.name])
    return arr.astype(np.dtype(comp.dtype), copy=False)


def stage_cache(plan: TransferPlan, pool, pages: Sequence[int],
                cache: Dict[str, object]) -> None:
    """Write a freshly computed cache pytree into pool slots (src side)."""
    schema = plan.schema
    pt = schema.page_tokens
    for ci, comp in enumerate(schema.components):
        arr = _comp_np(cache, comp)
        for s in range(comp.n_stack):
            layer = arr[s, 0]
            if comp.kind == "blob":
                data = np.ascontiguousarray(layer).reshape(-1).view(np.uint8)
                pool.write_slot(pages[plan.slot(ci, s, 0)], data)
                continue
            t_all = comp.tokens(plan.seq_len, plan.max_len)
            for c in range(plan.comp_chunks[ci]):
                lo, hi = c * pt, min(t_all, (c + 1) * pt)
                data = (np.ascontiguousarray(layer[lo:hi])
                        .reshape(-1).view(np.uint8))
                pool.write_slot(pages[plan.slot(ci, s, c)], data)


def fill_cache(plan: TransferPlan, pool, pages: Sequence[int],
               cache: Dict[str, object]) -> Dict[str, np.ndarray]:
    """Read pool slots back into cache arrays (dst side).

    ``cache`` supplies the target shapes (an ``init_cache`` pytree built
    with ``handoff_max_len(seq_len)``); returns ``{name: np.ndarray}`` for
    every schema component, leaving non-schema entries untouched."""
    schema = plan.schema
    pt = schema.page_tokens
    out: Dict[str, np.ndarray] = {}
    for ci, comp in enumerate(schema.components):
        base = np.array(_comp_np(cache, comp))      # writable copy
        dtype = np.dtype(comp.dtype)
        for s in range(comp.n_stack):
            if comp.kind == "blob":
                raw = pool.read_slot(pages[plan.slot(ci, s, 0)],
                                     comp.blob_bytes)
                base[s, 0] = raw.view(dtype).reshape(base.shape[2:])
                continue
            t_all = comp.tokens(plan.seq_len, plan.max_len)
            rest = base.shape[3:]
            for c in range(plan.comp_chunks[ci]):
                lo, hi = c * pt, min(t_all, (c + 1) * pt)
                raw = pool.read_slot(pages[plan.slot(ci, s, c)],
                                     (hi - lo) * comp.token_bytes)
                base[s, 0, lo:hi] = raw.view(dtype).reshape((hi - lo,) + rest)
        out[comp.name] = base
    return out
