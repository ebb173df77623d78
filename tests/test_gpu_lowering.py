"""GPU (Triton) lowering validation for the scan/accumulate kernels.

The TPU kernels' sequential-grid accumulators cannot compile on GPU, so
:mod:`repro.kernels.gpu_lowering` restructures them row-parallel. These
tests validate the lowering *logic* in Pallas interpret mode on every
backend (the CPU tier), check equivalence against BOTH the TPU kernels
(interpret) and the pure-jnp references, and — when a real CUDA/ROCm
device is present — compile the same kernels for the silicon path
(skip-marked elsewhere).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import gpu_lowering as gpu  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

HAS_GPU = ops.on_gpu()
needs_gpu = pytest.mark.skipif(not HAS_GPU, reason="no CUDA/ROCm device")


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(29)


# -------------------------------------------------------------- interpret
def test_compact_matches_tpu_kernel_and_ref(rng):
    from repro.kernels.compact import compact_positions_batched_pallas
    mask = (rng.random((5, 2048)) < 0.35).astype(np.int32)
    m = jnp.asarray(mask)
    pos_g, tot_g = gpu.compact_positions_batched_gpu(m, interpret=True)
    pos_t, tot_t = compact_positions_batched_pallas(m, interpret=True)
    np.testing.assert_array_equal(np.asarray(pos_g), np.asarray(pos_t))
    np.testing.assert_array_equal(np.asarray(tot_g), np.asarray(tot_t))
    incl = np.cumsum(mask, axis=1)
    np.testing.assert_array_equal(np.asarray(pos_g), incl - mask)
    np.testing.assert_array_equal(np.asarray(tot_g).ravel(), incl[:, -1])


def test_compact_single_stream_contract(rng):
    mask = (rng.random(1024) < 0.5).astype(np.int32)
    pos, tot = gpu.compact_positions_gpu(jnp.asarray(mask), interpret=True)
    incl = np.cumsum(mask)
    np.testing.assert_array_equal(np.asarray(pos), incl - mask)
    assert int(tot[0]) == int(incl[-1])


def test_metrics_bit_exact_hist_and_kahan_moments(rng):
    from repro.kernels.metrics_fused import stream_metrics_pallas
    ss = np.sort(rng.integers(0, 1500, (4, 2048)), axis=1).astype(np.int32)
    buckets = 1536
    h_g, m_g = gpu.stream_metrics_gpu(jnp.asarray(ss), buckets,
                                      interpret=True)
    h_t, m_t = stream_metrics_pallas(jnp.asarray(ss), buckets,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(h_g), np.asarray(h_t))
    # SAME Kahan block order as the TPU kernel -> bit-equal f32 moments
    np.testing.assert_array_equal(np.asarray(m_g), np.asarray(m_t))
    h_r, m_r = ref.stream_metrics_ref(jnp.asarray(ss), buckets)
    np.testing.assert_array_equal(np.asarray(h_g), np.asarray(h_r))
    np.testing.assert_allclose(np.asarray(m_g), np.asarray(m_r),
                               rtol=1e-5, atol=1e-2)


def test_metrics_padding_ids_count_nowhere(rng):
    ss = np.full((2, 1024), 10_000, np.int32)       # all padding stamps
    ss[0, :5] = [0, 1, 1, 2, 511]
    h, m = gpu.stream_metrics_gpu(jnp.asarray(ss), 512, interpret=True)
    h = np.asarray(h)
    assert h[0].sum() == 5 and h[1].sum() == 0
    assert h[0][1] == 2


def test_metrics_carry_composes_across_chunks(rng):
    from repro.kernels.metrics_fused import stream_metrics_carry_pallas
    buckets = 1024
    a = np.sort(rng.integers(0, buckets, (3, 1024)), axis=1) \
        .astype(np.int32)
    b = np.sort(rng.integers(0, buckets, (3, 1024)), axis=1) \
        .astype(np.int32)
    zero = jnp.zeros((3, 4), jnp.float32)
    h1, c1 = gpu.stream_metrics_carry_gpu(jnp.asarray(a), zero, buckets)
    h2, c2 = gpu.stream_metrics_carry_gpu(jnp.asarray(b), c1, buckets)
    h1t, c1t = stream_metrics_carry_pallas(jnp.asarray(a), zero, buckets,
                                           interpret=True)
    h2t, c2t = stream_metrics_carry_pallas(jnp.asarray(b), c1t, buckets,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h1t))
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(h2t))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c1t))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(c2t))


def test_trend_scan_bit_exact(rng):
    from repro.kernels.trend_scan import trend_scan_pallas
    q = rng.integers(0, 9, (6, 2048)).astype(np.int32)
    s_g = gpu.trend_scan_gpu(jnp.asarray(q), interpret=True)
    s_t = trend_scan_pallas(jnp.asarray(q), interpret=True)
    np.testing.assert_array_equal(np.asarray(s_g), np.asarray(s_t))
    np.testing.assert_array_equal(np.asarray(s_g), np.cumsum(q, axis=1))


def test_trend_scan_carry_contract(rng):
    from repro.kernels.trend_scan import trend_scan_carry_pallas
    q = rng.integers(0, 9, (4, 1024)).astype(np.int32)
    init = rng.integers(0, 1000, 4).astype(np.int32)
    p_g, t_g = gpu.trend_scan_carry_gpu(jnp.asarray(q), jnp.asarray(init))
    p_t, t_t = trend_scan_carry_pallas(jnp.asarray(q), jnp.asarray(init),
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(p_g), np.asarray(p_t))
    np.testing.assert_array_equal(np.asarray(t_g), np.asarray(t_t))
    np.testing.assert_array_equal(
        np.asarray(p_g), init[:, None] + np.cumsum(q, axis=1))


def test_pair_stats_within_tolerance(rng):
    from repro.kernels.trend_scan import pair_stats_pallas
    x = rng.standard_normal((5, 2048)).astype(np.float32)
    s_g, g_g = gpu.pair_stats_gpu(jnp.asarray(x), interpret=True)
    s_t, g_t = pair_stats_pallas(jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(np.asarray(s_g), np.asarray(s_t),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(g_g), np.asarray(g_t),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(g_g), x @ x.T,
                               rtol=1e-3, atol=1e-3)


def _sample_args(rng, widths=(600, 240), n=2048):
    """Range-padded (S, N) stream-sample inputs, one row per width."""
    width = max(widths)
    rows = [ops._nsa_tables(np.sort(rng.uniform(0, 900.0, n)), w, 3.0,
                            width) for w in widths]
    return tuple(jnp.asarray(np.stack([r[i] for r in rows]))
                 for i in range(4)) + (
        jnp.asarray(np.stack([r[4] for r in rows]), jnp.float32),), width


def test_stream_sample_matches_tpu_kernel_and_ref(rng):
    from repro.kernels.stream_sample import stream_sample_pallas
    args, width = _sample_args(rng)
    ss_g, keep_g = gpu.stream_sample_gpu(*args, width, interpret=True)
    ss_t, keep_t = stream_sample_pallas(*args, width, interpret=True)
    ss_r, keep_r = ref.stream_sample_ref(*args, width)
    for got, want in ((ss_g, ss_t), (keep_g, keep_t), (ss_g, ss_r),
                      (keep_g, keep_r)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_routes_stream_sample_to_gpu_lowering(rng, monkeypatch):
    ts = [np.sort(rng.uniform(0, 900.0, n)) for n in (1500, 700)]
    want = ops.stream_sample_batched(ts, [600, 240], 3.0)
    calls = []
    lowering = gpu.stream_sample_gpu

    def spy(*args, **kw):
        calls.append(args[5])
        return lowering(*args, **kw)

    monkeypatch.setattr(ops, "on_gpu", lambda: True)
    monkeypatch.setattr(gpu, "stream_sample_gpu", spy)
    got = ops.stream_sample_batched(ts, [600, 240], 3.0)
    assert calls == [600]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_smem_table_budget_binds_only_on_tpu(monkeypatch):
    from repro.kernels.stream_sample import MAX_TABLE_WIDTH
    t = np.arange(100, dtype=np.float64)
    wide = MAX_TABLE_WIDTH + 1
    assert len(ops._nsa_tables(t, wide, 2.0)[1]) == wide
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ops.PallasDomainError, match="SMEM"):
        ops._nsa_tables(t, wide, 2.0)
    assert len(ops._nsa_tables(t, MAX_TABLE_WIDTH, 2.0)[1]) == \
        MAX_TABLE_WIDTH


def test_ops_backend_auto_prefers_any_accelerator():
    # "auto" resolves to the pallas path whenever a real accelerator is
    # present (TPU *or* GPU) — the GPU lowering makes that safe
    from repro.streamsim.nsa import _resolve_backend
    expect = "pallas" if ops.on_accelerator() else "numpy"
    assert _resolve_backend("auto") == expect
    assert ops.on_accelerator() == (ops.on_tpu() or ops.on_gpu())


# ---------------------------------------------------------------- compiled
@needs_gpu
def test_compiled_compact_on_gpu(rng):
    mask = (rng.random((4, 4096)) < 0.3).astype(np.int32)
    pos, tot = gpu.compact_positions_batched_gpu(jnp.asarray(mask),
                                                 interpret=False)
    incl = np.cumsum(mask, axis=1)
    np.testing.assert_array_equal(np.asarray(pos), incl - mask)
    np.testing.assert_array_equal(np.asarray(tot).ravel(), incl[:, -1])


@needs_gpu
def test_compiled_metrics_on_gpu(rng):
    ss = np.sort(rng.integers(0, 900, (4, 4096)), axis=1).astype(np.int32)
    h, m = gpu.stream_metrics_gpu(jnp.asarray(ss), 1024, interpret=False)
    h_r, m_r = ref.stream_metrics_ref(jnp.asarray(ss), 1024)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_r))
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               rtol=1e-4, atol=1e-2)


@needs_gpu
def test_compiled_trend_and_pair_on_gpu(rng):
    q = rng.integers(0, 9, (4, 4096)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(gpu.trend_scan_gpu(jnp.asarray(q), interpret=False)),
        np.cumsum(q, axis=1))
    x = rng.standard_normal((4, 2048)).astype(np.float32)
    _, g = gpu.pair_stats_gpu(jnp.asarray(x), interpret=False)
    np.testing.assert_allclose(np.asarray(g), x @ x.T,
                               rtol=1e-3, atol=1e-3)


@needs_gpu
def test_compiled_stream_sample_on_gpu(rng):
    # ops dispatches the GPU lowering with interpret=False there
    args, width = _sample_args(rng)
    ss_c, keep_c = gpu.stream_sample_gpu(*args, width, interpret=False)
    ss_i, keep_i = gpu.stream_sample_gpu(*args, width, interpret=True)
    np.testing.assert_array_equal(np.asarray(ss_c), np.asarray(ss_i))
    np.testing.assert_array_equal(np.asarray(keep_c), np.asarray(keep_i))
