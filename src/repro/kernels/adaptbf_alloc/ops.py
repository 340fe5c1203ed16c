"""Dispatching wrapper for fleet-scale AdapTBF allocation: pads (O, J) to
hardware-friendly multiples, picks a VMEM-safe OST block, and routes to the
Pallas kernel (TPU, or interpret mode when forced) or the vmapped core
allocator."""
from __future__ import annotations

from repro.kernels.adaptbf_alloc import ref
from repro.kernels.adaptbf_alloc.kernel import fleet_alloc_pallas
from repro.kernels.dispatch import block_rows as _block_rows
from repro.kernels.dispatch import on_tpu as _on_tpu
from repro.kernels.dispatch import pad_lanes as _pad_lanes
from repro.kernels.dispatch import pad_to as _pad_to
from repro.kernels.dispatch import vmem_limit_bytes as _vmem_limit_bytes

# The top-k selection in core/remainder keeps ~16 live [block_o, J] f32
# arrays (inputs, outputs, selection temporaries) -- O(J) per row, so
# block_o stays 8 out to J=16384.  The old [block_o, J, J] rank matrix
# bound forced block_o=1 by J~1448 and could not fit J=4096 at all.
_LIVE_ROWS = 16


def fleet_alloc(demand, nodes, record, remainder, alloc_prev, capacity,
                *, u_max: float = 64.0, interpret: bool = None):
    """[O, J] arrays + [O] capacity -> (alloc, new_record, new_remainder).

    ``O`` may be the whole fleet or a per-device shard
    (``partition="ost_shard"``): the row block is capped at ``O`` so a
    small local slice is dispatched as exactly its own rows.
    """
    if interpret is None:
        interpret = not _on_tpu()
    o, j = demand.shape
    jp = _pad_lanes(j)
    bo = _block_rows(o)
    args = [_pad_to(_pad_to(x, jp, 1), bo, 0)
            for x in (demand, nodes, record, remainder, alloc_prev)]
    cap = _pad_to(capacity.reshape(-1), bo, 0)
    alloc, rec, rem = fleet_alloc_pallas(
        *args, cap, u_max=u_max, block_o=bo,
        vmem_limit_bytes=_vmem_limit_bytes(bo, jp, _LIVE_ROWS),
        interpret=interpret)
    return alloc[:o, :j], rec[:o, :j], rem[:o, :j]


fleet_alloc_ref = ref.fleet_alloc_ref
