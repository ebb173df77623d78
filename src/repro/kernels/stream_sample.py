"""Pallas TPU kernel: fused, batched NSA inner loop (normalize -> bucket ->
keep mask) over ``(S, N)`` stacked device streams.

One ``pallas_call`` with a 2-D grid ``(stream, record-tile)`` replaces S
sequential dispatches: grid step ``(s, i)`` normalizes an (8, 128)-record
tile of stream ``s`` while that stream's per-bucket tables (starts, counts,
per-bucket keep budget ``k``; ``max_range`` <= 3600 entries, <= 14 KiB each)
and scalars (t_min, 1/span, n_buckets) ride along in SMEM. The
single-stream path is just S == 1.

Range-padded batching: each row carries its OWN bucket count ``n_buckets``
in its scalar triple, so one dispatch can mix rows simulated at different
``max_range`` values — the whole (dataset × max_range) sweep of the paper's
Tables 1-3 collapses to a single kernel launch. The table axis is padded to
the sweep's maximum bucket count; tail buckets past a row's ``n_buckets``
never influence that row (the normalize clamps to ``n_buckets - 1`` and the
wrapper pads tails with ``starts = n``, ``counts = 0``, zero keep budget).
``n_buckets`` is shipped as float32, which represents every admissible
bucket count exactly (``MAX_RANGE_LIMIT = 2**20 < 2**24``), and the f32
normalize multiply is bit-identical to the static-``max_range`` form the
per-range dispatch used.

Exactness: the float32 normalize can land a record one bucket off the
float64 host answer near an edge, so the kernel *snaps*: the wrapper ships
per-bucket ``starts``/``counts`` tables computed with the host's exact
float64 formula, and the kernel corrects its f32 bucket guess by +-1 so that
``starts[b] <= gidx < starts[b] + counts[b]`` — because the stream is
sorted, the tables fully determine the true bucket, and the f32 guess is
provably within one bucket of it for ``max_range < 2**20``. Result: the
kernel's scale stamps and keep mask are bit-identical to the numpy NSA, not
just allclose. The table reads are a data-adaptive loop over the few
buckets a sorted tile spans (:func:`_lookup`): Mosaic has no vector gather
from a table, but it reads SMEM scalars at any index.

The per-bucket keep budget ``k = clip(round(count / multiple), 1)`` is also
precomputed host-side in float64 (an O(max_range) table), removing both the
per-record division and any f32 rounding drift from the kernel.

Domain: the keep rule's ``rank * k`` product is int32 (the TPU-native
width), exact only while ``(count - 1) * k < 2**31`` per bucket; the ops
wrapper raises :class:`repro.kernels.ops.KeepRuleOverflow` outside that
domain and ``nsa(backend="pallas")`` falls back to numpy.

Layout: the wrapper pads the record axis to a multiple of the tile and
reshapes to (S, rows, 128) so the lane dimension is hardware-native; record
blocks drop the row dim. Tables and scalars go in as (S, 1, width) arrays
whose (1, width) row blocks sit in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE  # records per grid step

# the +-1 snap correction is only guaranteed while the f32 normalize error
# stays under one bucket: ~4 * max_range * 2^-24 < 1
MAX_RANGE_LIMIT = 1 << 20

#: widest bucket table the kernel takes: three int32 tables per row,
#: double-buffered in SMEM (24 B per entry), must fit a v5e core's 1 MiB
#: of SMEM — 43 200 entries compile for v5e, 65 536 do not
MAX_TABLE_WIDTH = 40_960


def _lookup(tab_refs, bucket, lo, hi):
    """Per-record table lookup ``[tab[bucket] for tab in tab_refs]``.

    The tables sit in SMEM, so each entry is a scalar load; a sorted tile
    spans only the few buckets in ``[lo, hi]``, so the loop walks that span
    and selects each bucket's entries into the records that fall in it —
    a data-adaptive gather (the same trick as the metrics engine's
    bucket-block walk). Records outside ``[lo, hi]`` read 0."""
    def body(b, acc):
        hit = bucket == b
        return tuple(jnp.where(hit, ref[0, b], a)
                     for ref, a in zip(tab_refs, acc))

    init = tuple(jnp.zeros_like(bucket) for _ in tab_refs)
    return jax.lax.fori_loop(lo, hi + 1, body, init)


def _kernel(t_ref, starts_ref, counts_ref, k_ref, scalar_ref, ss_ref,
            keep_ref):
    i = pl.program_id(1)
    t = t_ref[...]                               # (SUBLANE, LANE) f32
    t_min = scalar_ref[0, 0]
    inv_span = scalar_ref[0, 1]                  # 1/span, precomputed
    nb_f = scalar_ref[0, 2]                      # this row's bucket count
    nb = jnp.full(t.shape, nb_f).astype(jnp.int32)   # f32-exact below 2**24

    # --- normalize: paper formula (1), floored to the simulated second ---
    g = jnp.floor((t - t_min) * inv_span * nb_f).astype(jnp.int32)
    g = jnp.clip(g, 0, nb - 1)

    base = i * TILE
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 1)
    gidx = base + row * LANE + col               # per-stream record index

    # --- snap the f32 guess to the bucket that actually contains gidx ---
    s_g, c_g = _lookup((starts_ref, counts_ref), g, jnp.min(g), jnp.max(g))
    g = g + (gidx >= s_g + c_g).astype(jnp.int32) \
          - (gidx < s_g).astype(jnp.int32)
    ss = jnp.clip(g, 0, nb - 1)

    # --- systematic keep: k of c survive, Bresenham-even ---
    start, c, k = _lookup((starts_ref, counts_ref, k_ref), ss,
                          jnp.min(ss), jnp.max(ss))
    rank = gidx - start
    keep = (rank * k) % jnp.maximum(c, 1) < k

    ss_ref[...] = ss
    keep_ref[...] = keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_range", "interpret"))
def stream_sample_pallas(t: jnp.ndarray, starts: jnp.ndarray,
                         counts: jnp.ndarray, ktab: jnp.ndarray,
                         scalars: jnp.ndarray, max_range: int, *,
                         interpret: bool = False):
    """Batched fused NSA inner loop (range-padded rows).

    t       : (S, N) float32 per-stream rebased timestamps, sorted along the
              record axis, N % TILE == 0 (pad tails with any finite value —
              padded keep bits are garbage; the wrapper masks by length).
    starts  : (S, max_range) int32 exact per-bucket start offsets; tail
              entries past a row's ``n_buckets`` must be the record count.
    counts  : (S, max_range) int32 exact per-bucket sizes (0 past
              ``n_buckets``).
    ktab    : (S, max_range) int32 per-bucket keep budgets (0 past
              ``n_buckets`` — the masked tail keeps nothing).
    scalars : (S, 3) float32 rows of (t_min, 1/span, n_buckets) with
              ``n_buckets <= max_range`` the row's own bucket count.

    ``max_range`` is only the padded TABLE width; per-row compute uses the
    ``n_buckets`` scalar, so rows at different time ranges batch into one
    dispatch. Returns (scale_stamp int32 (S, N), keep int32 (S, N)).
    """
    S, n = t.shape
    assert n % TILE == 0, f"pad records to a multiple of {TILE}"
    assert max_range <= MAX_RANGE_LIMIT, \
        f"max_range {max_range} too large for the +-1 bucket snap"
    rows = n // LANE
    t3 = t.reshape(S, rows, LANE)
    grid = (S, rows // SUBLANE)
    # per-row tables and scalars ride in SMEM as (1, width) blocks of an
    # (S, 1, width) layout; record tiles drop the row dim
    tile_spec = pl.BlockSpec((None, SUBLANE, LANE), lambda s, i: (s, i, 0))

    def smem_row(width):
        return pl.BlockSpec((None, 1, width), lambda s, i: (s, 0, 0),
                            memory_space=pltpu.SMEM)

    ss, keep = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[tile_spec, smem_row(max_range), smem_row(max_range),
                  smem_row(max_range), smem_row(3)],
        out_specs=[tile_spec, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((S, rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((S, rows, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(t3, starts[:, None, :], counts[:, None, :], ktab[:, None, :],
      scalars[:, None, :])
    return ss.reshape(S, n), keep.reshape(S, n)
