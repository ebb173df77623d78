"""Pallas TPU kernel: mask compaction via a tiled exclusive prefix sum.

Turns a 0/1 keep mask into the write position of every record (``pos[i] =
number of kept records before i``) plus the total kept count, in ONE
sequential HBM pass: the TPU grid walks record tiles in order, each step
computing the tile-local exclusive prefix sum (:func:`tile_prefix_sum`, an
exact log-step scan) and adding the running carry held in SMEM scratch —
the classic single-pass scan-with-carry, no second kernel launch and no
host round-trip. Totals leave as lane-dense ``(1, 128)`` blocks.

The caller turns positions into gathered kept-record *indices* with one XLA
scatter (``zeros.at[pos[kept]].set(iota)``, see :func:`repro.kernels.ops.
compact_mask`) — TPUs have no fast in-kernel scatter, but a dense
length-``n`` scatter with device-computed positions is a single additional
bandwidth pass and keeps the whole NSA chain on device.

Layout mirrors the other kernels: records padded to a multiple of the
(8, 128) tile; padded entries must carry mask ``0``.

The kernel's grid is 2-D, ``(row, record-tile)`` — the carry resets at
each row's first tile (the :mod:`repro.kernels.trend_scan` pattern), so
``compact_positions_batched_pallas`` compacts R rows' keep masks in ONE
dispatch with per-row totals, and ``compact_positions_pallas`` is its
one-row case. This is the compaction leg of the range-padded NSA sweep:
every (dataset × max_range) scenario is a row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import DEFAULT_CONFIG, TileConfig

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE  # records per grid step with the default TileConfig


def tile_prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a ``(sublane, LANE)`` int32 tile in
    row-major record order, exact.

    Hillis-Steele log-step scan: 7 shifted adds along the lanes
    (``pltpu.roll`` plus an iota mask that drops the wrapped-around
    entries), then the row totals broadcast across lanes and scan the same
    way along the sublanes (3 steps for 8 rows) into per-row offsets.
    Integer adds only, so the result is exact — Mosaic has no ``cumsum``
    lowering, and a matmul form would round on the MXU's f32 passes."""
    sublane = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < LANE:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    row_tot = jnp.broadcast_to(x[:, LANE - 1:], x.shape)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    off = row_tot
    shift = 1
    while shift < sublane:
        off = off + jnp.where(row >= shift, pltpu.roll(off, shift, 0), 0)
        shift *= 2
    return x + off - row_tot                         # exclusive row offsets


def _kernel(mask_ref, pos_ref, total_ref, carry_ref):
    @pl.when(pl.program_id(1) == 0)
    def _reset():                                    # new row: fresh carry
        carry_ref[0] = 0

    m = mask_ref[...].astype(jnp.int32)              # (sublane, LANE) 0/1
    carry = carry_ref[0]
    pos_ref[...] = carry + tile_prefix_sum(m) - m
    total = carry + jnp.sum(m)
    carry_ref[0] = total
    # lane-dense total block; the row's last tile writes the final value
    total_ref[...] = jnp.full(total_ref.shape, total, jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "config"))
def compact_positions_pallas(mask: jnp.ndarray, *, interpret: bool = False,
                             config: Optional[TileConfig] = None):
    """mask: (n,) int32 0/1, n % TILE == 0 (pad with 0).

    Returns ``(pos int32 (n,), total int32 (1,))`` where ``pos[i]`` is the
    exclusive prefix sum of the mask (the output slot of record ``i`` if it
    is kept) and ``total`` the number of set mask entries — the one-row case
    of :func:`compact_positions_batched_pallas`.
    """
    pos, totals = compact_positions_batched_pallas(
        mask[None, :], interpret=interpret, config=config)
    return pos[0], totals[0]


@functools.partial(jax.jit, static_argnames=("interpret", "config"))
def compact_positions_batched_pallas(mask: jnp.ndarray, *,
                                     interpret: bool = False,
                                     config: Optional[TileConfig] = None):
    """Batched mask compaction: R rows' scans in ONE 2-D-grid dispatch.

    mask: (R, N) int32 0/1, N % TILE == 0 (pad record tails with 0).

    Returns ``(pos int32 (R, N), totals int32 (R, 1))`` — per row the same
    contract as :func:`compact_positions_pallas`: ``pos[r, i]`` is the
    exclusive prefix sum of row ``r``'s mask and ``totals[r]`` its set-entry
    count. The SMEM carry resets at each row's first record tile, so rows
    are independent (bit-identical to R sequential single-row dispatches).
    """
    cfg = DEFAULT_CONFIG if config is None else config
    sublane = cfg.sublane
    R, n = mask.shape
    assert n % cfg.record_tile == 0, \
        f"pad records to a multiple of {cfg.record_tile}"
    rows = n // LANE
    m3 = mask.reshape(R, rows, LANE)
    grid = (R, rows // sublane)
    tile_spec = pl.BlockSpec((None, sublane, LANE), lambda r, i: (r, i, 0))
    pos, totals = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[tile_spec],
        out_specs=[tile_spec,
                   pl.BlockSpec((None, 1, LANE), lambda r, i: (r, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((R, rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((R, 1, LANE), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(m3)
    return pos.reshape(R, n), totals[:, 0, :1]
