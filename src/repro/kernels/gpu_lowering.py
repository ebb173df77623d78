"""Pallas GPU (Triton) lowerings of the stream kernels.

The TPU kernels lean on two TPU-only guarantees: a SEQUENTIAL grid (later
grid steps observe earlier steps' writes to the same output block — the
histogram / Gram accumulators whose index maps ignore the tile index, and
the SMEM scan carries) and ``pltpu`` scratch memory. Triton launches grid
steps CONCURRENTLY, so compiling those kernels on GPU would race. These
lowerings restructure each op as a ROW-PARALLEL kernel instead: the grid
ranges over stream rows only, every instance owns one whole row, and all
cross-tile state collapses into in-kernel ``cumsum`` / ``fori_loop`` state
that never leaves the instance.

Contracts match the TPU kernels':

- compact / trend scan: int32 prefix sums, bit-exact (integer arithmetic
  has no reassociation error, so a row-wise ``cumsum`` equals the TPU
  tile-walk exactly).
- metrics: bit-exact int32 histograms; f32 moments folded with the SAME
  per-bucket-block Kahan order as the TPU kernel, so the chunked-carry
  composition keeps its ~1e-5 agreement.
- pair stats: one whole-axis f32 matmul per instance (vs. the TPU
  tile-accumulated MXU walk) — inside the documented 1e-3 tolerance.

- stream sample: the same grid-parallel kernel body (each step reads and
  writes only its own record tile), with the bucket tables as ordinary
  row blocks read by ``jnp.take`` instead of the TPU kernel's SMEM walk —
  scale stamps and keep mask bit-identical.

Off-GPU these kernels still run under ``interpret=True`` — that is how the
CPU test tier validates the lowering logic without the hardware
(``tests/test_gpu_lowering.py``); the compiled path is exercised by the
same tests when a CUDA/ROCm device is present.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_GPU_BACKENDS = ("gpu", "cuda", "rocm")


def _interp() -> bool:
    """interpret=True everywhere except a real GPU backend."""
    return jax.default_backend() not in _GPU_BACKENDS


# ----------------------------------------------------------- stream sample
_LANE = 128


def _sample_kernel(t_ref, starts_ref, counts_ref, k_ref, scalar_ref, ss_ref,
                   keep_ref, *, sublane: int):
    i = pl.program_id(1)
    t = t_ref[0]                                  # (sublane, LANE) f32
    t_min = scalar_ref[0, 0]
    inv_span = scalar_ref[0, 1]
    nb_f = scalar_ref[0, 2]
    nb = nb_f.astype(jnp.int32)
    starts, counts, ktab = starts_ref[0], counts_ref[0], k_ref[0]

    g = jnp.floor((t - t_min) * inv_span * nb_f).astype(jnp.int32)
    g = jnp.clip(g, 0, nb - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (sublane, _LANE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sublane, _LANE), 1)
    gidx = i * sublane * _LANE + row * _LANE + col

    s_g = jnp.take(starts, g, axis=0)
    c_g = jnp.take(counts, g, axis=0)
    g = g + (gidx >= s_g + c_g).astype(jnp.int32) \
          - (gidx < s_g).astype(jnp.int32)
    ss = jnp.clip(g, 0, nb - 1)

    start = jnp.take(starts, ss, axis=0)
    c = jnp.take(counts, ss, axis=0)
    k = jnp.take(ktab, ss, axis=0)
    keep = ((gidx - start) * k) % jnp.maximum(c, 1) < k
    ss_ref[0] = ss
    keep_ref[0] = keep.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("max_range", "sublane", "interpret"))
def stream_sample_gpu(t: jnp.ndarray, starts: jnp.ndarray,
                      counts: jnp.ndarray, ktab: jnp.ndarray,
                      scalars: jnp.ndarray, max_range: int, *,
                      sublane: int = 8, interpret: bool = None):
    """Grid-parallel fused NSA inner loop: (S, N) rebased timestamps plus
    (S, max_range) tables and (S, 3) scalars -> (scale_stamp int32 (S, N),
    keep int32 (S, N)) — the
    :func:`repro.kernels.stream_sample.stream_sample_pallas` contract."""
    if interpret is None:
        interpret = _interp()
    S, n = t.shape
    assert n % (sublane * _LANE) == 0
    rows = n // _LANE
    tile_spec = pl.BlockSpec((1, sublane, _LANE), lambda s, i: (s, i, 0))

    def row_spec(width):
        return pl.BlockSpec((1, width), lambda s, i: (s, 0))

    ss, keep = pl.pallas_call(
        functools.partial(_sample_kernel, sublane=sublane),
        grid=(S, rows // sublane),
        in_specs=[tile_spec, row_spec(max_range), row_spec(max_range),
                  row_spec(max_range), row_spec(3)],
        out_specs=[tile_spec, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((S, rows, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((S, rows, _LANE), jnp.int32),
        ],
        interpret=interpret,
    )(t.reshape(S, rows, _LANE).astype(jnp.float32), starts, counts, ktab,
      scalars.astype(jnp.float32))
    return ss.reshape(S, n), keep.reshape(S, n)


# ----------------------------------------------------------------- compact
def _compact_kernel(m_ref, pos_ref, tot_ref):
    m = m_ref[0]                                  # (N,) int32 row
    inc = jnp.cumsum(m, dtype=jnp.int32)
    pos_ref[0] = inc - m                          # exclusive prefix sum
    tot_ref[0, 0] = inc[-1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_positions_batched_gpu(mask: jnp.ndarray, *,
                                  interpret: bool = None):
    """Row-parallel batched compaction scan: (R, N) 0/1 mask ->
    (pos int32 (R, N) exclusive prefix sums, totals int32 (R, 1)) — the
    :func:`repro.kernels.compact.compact_positions_batched_pallas`
    contract, shapes included."""
    if interpret is None:
        interpret = _interp()
    R, n = mask.shape
    pos, tot = pl.pallas_call(
        _compact_kernel,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, n), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((1, n), lambda r: (r, 0)),
            pl.BlockSpec((1, 1), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, n), jnp.int32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
        ],
        interpret=interpret,
    )(mask.astype(jnp.int32))
    return pos, tot


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_positions_gpu(mask: jnp.ndarray, *, interpret: bool = None):
    """Single-stream form: (n,) mask -> (pos (n,), total (1,)) — the
    :func:`repro.kernels.compact.compact_positions_pallas` contract."""
    pos, tot = compact_positions_batched_gpu(mask[None, :],
                                             interpret=interpret)
    return pos[0], tot[0]


# ----------------------------------------------------------------- metrics
def _hist_blocks(ss, hist_ref, *, buckets: int, bucket_block: int):
    """Bucket-blocked one-hot histogram of one row's stamps; padding ids
    (>= buckets) match no bucket and count nowhere — same trick as the
    TPU kernel, minus the data-adaptive lo/hi clip (one instance owns the
    whole row, so every block must be written anyway)."""

    def body(blk, carry):
        base = blk * bucket_block
        ids = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, bucket_block), 1)
        one = (ss[:, None] == ids).astype(jnp.int32)  # (N, bucket_block)
        hist_ref[0, pl.ds(base, bucket_block)] = jnp.sum(one, axis=0)
        return carry

    jax.lax.fori_loop(0, buckets // bucket_block, body, 0)


def _kahan_fold(hist_ref, init, *, buckets: int, bucket_block: int):
    """The TPU kernels' exact per-block Kahan recurrence over the finished
    histogram — same block order, same compensated-add formula."""

    def kahan(blk, carry):
        s1, c1, s2, c2 = carry
        q = hist_ref[0, pl.ds(blk * bucket_block, bucket_block)] \
            .astype(jnp.float32)
        y1 = jnp.sum(q) - c1
        t1 = s1 + y1
        y2 = jnp.sum(q * q) - c2
        t2 = s2 + y2
        return t1, (t1 - s1) - y1, t2, (t2 - s2) - y2

    return jax.lax.fori_loop(0, buckets // bucket_block, kahan, init)


def _metrics_kernel(ss_ref, hist_ref, mom_ref, *, buckets: int,
                    bucket_block: int):
    ss = ss_ref[0]                                # (N,) int32 row
    _hist_blocks(ss, hist_ref, buckets=buckets, bucket_block=bucket_block)
    zero = jnp.float32(0.0)
    s1, _, s2, _ = _kahan_fold(hist_ref, (zero, zero, zero, zero),
                               buckets=buckets, bucket_block=bucket_block)
    mom_ref[0, 0] = s1
    mom_ref[0, 1] = s2


def _metrics_carry_kernel(ss_ref, mcar_ref, hist_ref, mom_ref, *,
                          buckets: int, bucket_block: int):
    ss = ss_ref[0]
    _hist_blocks(ss, hist_ref, buckets=buckets, bucket_block=bucket_block)
    s1, c1, s2, c2 = _kahan_fold(
        hist_ref,
        (mcar_ref[0, 0], mcar_ref[0, 1], mcar_ref[0, 2], mcar_ref[0, 3]),
        buckets=buckets, bucket_block=bucket_block)
    mom_ref[0, 0] = s1
    mom_ref[0, 1] = c1
    mom_ref[0, 2] = s2
    mom_ref[0, 3] = c2


@functools.partial(jax.jit,
                   static_argnames=("buckets", "bucket_block", "interpret"))
def stream_metrics_gpu(ss: jnp.ndarray, buckets: int, *,
                       bucket_block: int = 512, interpret: bool = None):
    """Row-parallel fused metrics: (S, N) stamps -> (hist int32
    (S, buckets), moments f32 (S, 2)) — the
    :func:`repro.kernels.metrics_fused.stream_metrics_pallas` contract."""
    if interpret is None:
        interpret = _interp()
    assert buckets % bucket_block == 0
    S, n = ss.shape
    return pl.pallas_call(
        functools.partial(_metrics_kernel, buckets=buckets,
                          bucket_block=bucket_block),
        grid=(S,),
        in_specs=[pl.BlockSpec((1, n), lambda s: (s, 0))],
        out_specs=[
            pl.BlockSpec((1, buckets), lambda s: (s, 0)),
            pl.BlockSpec((1, 2), lambda s: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, buckets), jnp.int32),
            jax.ShapeDtypeStruct((S, 2), jnp.float32),
        ],
        interpret=interpret,
    )(ss.astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("buckets", "bucket_block", "interpret"))
def stream_metrics_carry_gpu(ss: jnp.ndarray, mcar: jnp.ndarray,
                             buckets: int, *, bucket_block: int = 512,
                             interpret: bool = None):
    """Carry form: (S, 4) Kahan state in, chunk-local hist + updated
    (S, 4) state out — the ``stream_metrics_carry_pallas`` contract."""
    if interpret is None:
        interpret = _interp()
    assert buckets % bucket_block == 0
    S, n = ss.shape
    return pl.pallas_call(
        functools.partial(_metrics_carry_kernel, buckets=buckets,
                          bucket_block=bucket_block),
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, n), lambda s: (s, 0)),
            pl.BlockSpec((1, 4), lambda s: (s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, buckets), lambda s: (s, 0)),
            pl.BlockSpec((1, 4), lambda s: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, buckets), jnp.int32),
            jax.ShapeDtypeStruct((S, 4), jnp.float32),
        ],
        interpret=interpret,
    )(ss.astype(jnp.int32), mcar.astype(jnp.float32))


# -------------------------------------------------------------- trend scan
def _scan_kernel(q_ref, psum_ref):
    psum_ref[0] = jnp.cumsum(q_ref[0].astype(jnp.int32), dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def trend_scan_gpu(q: jnp.ndarray, *, interpret: bool = None):
    """Row-parallel inclusive prefix sum: (S, N) int32 -> (S, N) int32."""
    if interpret is None:
        interpret = _interp()
    S, n = q.shape
    return pl.pallas_call(
        _scan_kernel,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, n), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((1, n), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, n), jnp.int32),
        interpret=interpret,
    )(q.astype(jnp.int32))


def _scan_carry_kernel(init_ref, q_ref, psum_ref, tail_ref):
    inc = init_ref[0, 0] + jnp.cumsum(q_ref[0].astype(jnp.int32),
                                      dtype=jnp.int32)
    psum_ref[0] = inc
    tail_ref[0, 0] = inc[-1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def trend_scan_carry_gpu(q: jnp.ndarray, init: jnp.ndarray, *,
                         interpret: bool = None):
    """Carry form: per-row carry-in seeds the scan; returns
    (psum (S, N), tail (S,)) — the ``trend_scan_carry_pallas`` contract."""
    if interpret is None:
        interpret = _interp()
    S, n = q.shape
    psum, tail = pl.pallas_call(
        _scan_carry_kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda s: (s, 0)),
            pl.BlockSpec((1, n), lambda s: (s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda s: (s, 0)),
            pl.BlockSpec((1, 1), lambda s: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, n), jnp.int32),
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
        ],
        interpret=interpret,
    )(init.reshape(S, 1).astype(jnp.int32), q.astype(jnp.int32))
    return psum, tail.reshape(S)


# -------------------------------------------------------------- pair stats
def _pair_kernel(x_ref, sums_ref, gram_ref):
    x = x_ref[...]                                # (S, K) f32
    sums_ref[...] = jnp.sum(x, axis=1, keepdims=True)
    gram_ref[...] = jnp.dot(x, x.T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_stats_gpu(x: jnp.ndarray, *, interpret: bool = None):
    """One whole-axis Gram matmul: (S, K) f32 -> (sums (S, 1),
    gram (S, S)) — the ``pair_stats_pallas`` contract."""
    if interpret is None:
        interpret = _interp()
    S, k = x.shape
    return pl.pallas_call(
        _pair_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((S, k), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
            pl.BlockSpec((S, S), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, S), jnp.float32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32))


__all__ = [
    "compact_positions_batched_gpu", "compact_positions_gpu",
    "pair_stats_gpu", "stream_metrics_carry_gpu", "stream_metrics_gpu",
    "stream_sample_gpu", "trend_scan_carry_gpu", "trend_scan_gpu",
]
