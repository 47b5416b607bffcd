"""TPU-native weight resharding: §5's static schedule as an XLA program.

On TPU, the paper's P2P weight push (training sharding -> inference
sharding) is a *resharding*: a jitted identity whose input sharding is the
trainer's (FSDP-style, data-axis sharded) and whose output sharding is the
server's (TP, model-axis sharded).  GSPMD emits the minimal
collective-permute/all-to-all schedule — the XLA analogue of the paper's
controller-computed route table — while the baseline gathers to a fully
replicated copy first (the rank0 pattern).

``reshard_plan`` compiles both and reports the collective bytes each moves,
giving the P2P-vs-rank0 comparison in HLO terms.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..roofline.hlo_cost import analyze_hlo


def _identity(tree):
    return jax.tree.map(lambda x: x, tree)


def build_reshard(mesh: Mesh, shapes, src_specs, dst_specs):
    """Compile tree-reshard(src sharding -> dst sharding).  Returns
    (compiled, collective_bytes_per_device)."""
    src = jax.tree.map(lambda s: NamedSharding(mesh, s), src_specs,
                       is_leaf=lambda x: isinstance(x, P))
    dst = jax.tree.map(lambda s: NamedSharding(mesh, s), dst_specs,
                       is_leaf=lambda x: isinstance(x, P))
    fn = jax.jit(_identity, in_shardings=(src,), out_shardings=dst)
    compiled = fn.lower(shapes).compile()
    cost = analyze_hlo(compiled.as_text())
    return compiled, cost


def fsdp_to_tp(x, mesh: Mesh, *, daxes=("data",), ep_axis: str = "model"):
    """Explicit FSDP(row-sharded over all axes) -> TP(col-sharded) reshard.

    GSPMD's fallback for this transpose is full rematerialisation (it warns
    'Involuntary full rematerialization'): replicate, then re-slice — every
    device receives the whole tensor.  The paper's insight applies on TPU
    too: an explicit schedule (slice the destination column block locally,
    then all-gather only those rows) moves ``1/tp`` of the bytes.

    x: (R, C) row-sharded over (daxes..., ep_axis); returns (R, C)
    col-sharded over ep_axis (replicated over daxes).
    """
    import jax.numpy as jnp
    tp = mesh.shape[ep_axis]
    all_axes = tuple(daxes) + (ep_axis,)

    def local(x_l):
        # 1. all_to_all on the TP axis: send each destination ITS column
        #    block; receive my column block's rows from every TP peer
        r, c = x_l.shape
        blocks = x_l.reshape(r, tp, c // tp).transpose(1, 0, 2)   # (tp, r, c/tp)
        mine = jax.lax.all_to_all(blocks, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        mine = mine.reshape(tp * r, c // tp)
        # 2. all_gather the remaining row shards over the data axes
        if daxes:
            mine = jax.lax.all_gather(mine, tuple(daxes), axis=0, tiled=True)
        return mine

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=P((*daxes, ep_axis), None),
        out_specs=P(None, ep_axis), check_vma=False)(x)


def reshard_plan(mesh: Mesh, shapes, train_specs, infer_specs) -> Dict:
    """P2P reshard vs gather-to-replicated baseline, in collective bytes."""
    _, direct = build_reshard(mesh, shapes, train_specs, infer_specs)
    repl = jax.tree.map(lambda s: P(*([None] * len(s))), train_specs,
                        is_leaf=lambda x: isinstance(x, P))
    _, gather = build_reshard(mesh, shapes, train_specs, repl)
    _, scatter = build_reshard(mesh, shapes, repl, infer_specs)
    # explicit fabric-lib-style schedule for the 2D FSDP->TP leaves
    import jax.numpy as jnp
    daxes = tuple(a for a in mesh.axis_names if a != "model")
    smart_bytes = 0.0
    try:
        two_d = {k: v for k, v in shapes.items()
                 if len(getattr(v, "shape", ())) == 2}
        if two_d:
            fn = jax.jit(lambda t: {k: fsdp_to_tp(v, mesh, daxes=daxes)
                                    for k, v in t.items()})
            comp = fn.lower(two_d).compile()
            smart_bytes = analyze_hlo(comp.as_text()).coll_wire_bytes
    except Exception:
        smart_bytes = float("nan")
    return {
        "gspmd_wire_bytes": direct.coll_wire_bytes,
        "gspmd_breakdown": direct.coll_breakdown,
        "smart_wire_bytes": smart_bytes,
        "rank0_wire_bytes": gather.coll_wire_bytes + scatter.coll_wire_bytes,
        "smart_vs_gspmd": direct.coll_wire_bytes / max(smart_bytes, 1.0),
    }
