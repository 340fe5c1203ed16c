"""Shared dispatch helpers for the kernel packages.

Every ``ops.py`` dispatcher needs the same three things: the
``REPRO_FORCE_REF_KERNELS`` escape hatch (read once at import, before any
kernel module -- ``tests/conftest.py`` sets it ahead of imports off-TPU),
the TPU predicate, and padding to hardware-friendly block multiples.  One
definition here so the packages cannot drift."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

FORCE_REF = os.environ.get("REPRO_FORCE_REF_KERNELS", "0") == "1"


def on_tpu() -> bool:
    return (not FORCE_REF) and jax.default_backend() == "tpu"


def pad_to(x, m, axis, value=0.0):
    """Zero-extend (or ``value``-extend) ``x`` so ``x.shape[axis] % m == 0``."""
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def pad_lanes(j: int) -> int:
    """Job-axis size padded up to the TPU lane multiple (128)."""
    return max(128, j + (-j) % 128)


#: Mosaic's row tile: a block's second-to-last dimension must be a multiple
#: of 8 or the array's whole extent
ROW_TILE = 8
#: Mosaic's default scoped-VMEM limit on v5e; a kernel asks for more only
#: when its estimated working set exceeds it
DEFAULT_SCOPED_VMEM = 16 * 2**20
#: the most scoped VMEM a kernel may request (v5e and v6e hold 128 MiB of
#: VMEM per core; the rest stays with Mosaic's own scratch)
MAX_SCOPED_VMEM = 100 * 2**20


def block_rows(n_rows: int) -> int:
    """The OST block of every row-blocked kernel: one row tile (8 rows), or
    the whole row count when a (sharded-local) slice holds fewer.

    A sharded engine (``partition="ost_shard"``) handing each device a
    small local slice is dispatched as exactly its own rows -- never padded
    out to an 8-row block.  A wide job axis raises the kernel's scoped
    VMEM (``vmem_limit_bytes``) instead of shrinking the block below the
    tile, which Mosaic refuses.  One definition for every kernel package so
    row-block policy cannot drift between dispatchers.
    """
    return min(ROW_TILE, max(n_rows, 1))


def vmem_limit_bytes(block: int, j: int, live_rows: int) -> int:
    """Scoped VMEM to request for a kernel keeping ``live_rows`` [block, J]
    f32 arrays live (inputs + outputs + temporaries).  The estimate is 2.5x
    their bytes: Mosaic double-buffers every pipelined block and keeps the
    body's temporaries beside them (at J = 16384 fleet_window needs 20.8 MiB
    for 20 live rows, window_mega 29 MiB for 42).  Never below Mosaic's
    default; a shape whose estimate exceeds ``MAX_SCOPED_VMEM`` is refused
    by name."""
    need = 5 * live_rows * block * j * 4 // 2
    if need > MAX_SCOPED_VMEM:
        raise ValueError(
            f"a [{block}, {j}] row block with {live_rows} live arrays needs "
            f"~{need / 2**20:.0f} MiB of VMEM, over the "
            f"{MAX_SCOPED_VMEM // 2**20} MiB a kernel may request; shorten "
            "the job axis or the window")
    return max(need, DEFAULT_SCOPED_VMEM)
