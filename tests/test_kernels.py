"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.stream_sample import TILE


def _sorted_times(n, span, seed, dtype=np.float64):
    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0, span, n)).astype(dtype)
    t[0], t[-1] = 0.0, span
    return t


class TestStreamSample:
    @pytest.mark.parametrize("n", [64, TILE - 1, 1024, TILE + 1, 4096,
                                   10_000])
    @pytest.mark.parametrize("max_range", [16, 128, 600])
    def test_matches_oracle(self, n, max_range):
        t = _sorted_times(n, 86_400.0, seed=n + max_range)
        mult = 86_400.0 / max_range
        ss_k, keep_k = ops.stream_sample(t, max_range, mult)
        ss_o, keep_o = ops.stream_sample_ref(t, max_range, mult)
        np.testing.assert_array_equal(np.asarray(ss_k), np.asarray(ss_o))
        np.testing.assert_array_equal(np.asarray(keep_k), np.asarray(keep_o))

    def test_matches_host_nsa_exactly(self):
        # the +-1 bucket snap against exact f64 tables makes the kernel
        # bit-identical to the host path, not merely close
        from repro.streamsim.nsa import scale_stamps, systematic_keep_mask
        t = _sorted_times(20_000, 86_400.0, seed=1)
        mr, mult = 300, 86_400.0 / 300
        ss_np = scale_stamps(t, mr)
        keep_np = systematic_keep_mask(ss_np, mr, mult)
        ss_k, keep_k = ops.stream_sample(t, mr, mult)
        np.testing.assert_array_equal(np.asarray(ss_k), ss_np)
        np.testing.assert_array_equal(np.asarray(keep_k), keep_np)

    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    def test_dtypes(self, dtype):
        t = _sorted_times(TILE, 1000.0, seed=3, dtype=dtype)
        ss, keep = ops.stream_sample(t, 50, 20.0)
        assert ss.dtype == jnp.int32
        assert int(keep.sum()) >= 50 // 2

    def test_keep_rule_overflow_refused(self):
        # (c-1)*k >= 2**31 would wrap the int32 Bresenham product and
        # silently diverge from the int64 numpy path — must raise instead
        t = np.full(100_000, 5.0)
        with pytest.raises(ops.KeepRuleOverflow):
            ops.stream_sample(t, 600, 3.0)
        with pytest.raises(ops.KeepRuleOverflow):
            ops.stream_sample_batched([t], 600, 3.0)

    def test_max_range_beyond_snap_limit_refused(self):
        # beyond the +-1 snap guarantee the wrapper must refuse (not assert)
        from repro.kernels.stream_sample import MAX_RANGE_LIMIT
        t = np.arange(100, dtype=np.float64)
        with pytest.raises(ops.PallasDomainError):
            ops.stream_sample(t, MAX_RANGE_LIMIT + 1, 2.0)
        # ...and nsa() falls back to numpy instead of surfacing the error
        from repro.streamsim.nsa import nsa as nsa_fn
        from repro.streamsim.preprocess import Stream
        s = Stream("x", t, {"v": np.arange(100)})
        a = nsa_fn(s, MAX_RANGE_LIMIT + 1, backend="pallas")
        b = nsa_fn(s, MAX_RANGE_LIMIT + 1, backend="numpy")
        np.testing.assert_array_equal(a.t, b.t)

    @pytest.mark.parametrize("n", [1, 7, 500])
    def test_zero_span_stream(self, n):
        # all-equal timestamps: host path puts everything in bucket 0; the
        # degenerate table branch must agree (regression: records used to
        # land in bucket 1 via the snap)
        from repro.streamsim.nsa import scale_stamps, systematic_keep_mask
        t = np.full(n, 1234.5)
        ss, keep = ops.stream_sample(t, 600, 144.0)
        np.testing.assert_array_equal(np.asarray(ss), scale_stamps(t, 600))
        np.testing.assert_array_equal(
            np.asarray(keep), systematic_keep_mask(np.zeros(n, np.int64),
                                                   600, 144.0))

    def test_smem_table_budget_binds_only_for_the_tpu(self, monkeypatch):
        from repro.kernels.stream_sample import MAX_TABLE_WIDTH
        t = np.arange(100, dtype=np.float64)
        wide = MAX_TABLE_WIDTH + 1
        assert len(ops._nsa_tables(t, wide, 2.0)[0]) == wide
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        with pytest.raises(ops.PallasDomainError, match="SMEM"):
            ops._nsa_tables(t, wide, 2.0)
        assert len(ops._nsa_tables(t, MAX_TABLE_WIDTH, 2.0)[0]) == \
            MAX_TABLE_WIDTH


@pytest.mark.parametrize("tpu", [False, True])
def test_backend_auto_resolves_to_pallas_iff_the_tpu(tpu, monkeypatch):
    # the kernels compile only for the TPU; elsewhere "auto" keeps to the
    # host path (interpret mode is correct but slower than numpy)
    from repro.streamsim.nsa import _resolve_backend
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    assert _resolve_backend("auto") == ("pallas" if tpu else "numpy")


def _starts_by_searchsorted(t64, max_range, width):
    """Bucket starts from the full float64 ``v`` plane: the formula the
    bisection in ``ops._nsa_tables`` must reproduce bit for bit."""
    n = len(t64)
    starts = np.full(width, n, np.int64)
    span = float(t64[-1]) - float(t64[0])
    if span <= 0.0:
        starts[0] = 0
    else:
        v = (t64 - float(t64[0])) / span * max_range
        starts[:max_range] = np.searchsorted(v, np.arange(max_range))
    return starts


def _edge_ties():
    # runs of equal stamps on every bucket edge of range 600 over a day,
    # with neighbours one ulp either side: ties straddle the edges
    edges = np.arange(601) * 144.0
    return np.sort(np.concatenate([
        np.repeat(edges, 3), np.nextafter(edges, -np.inf)[1:],
        np.nextafter(edges, np.inf)[:-1]])), 600, 600


def _on_integers():
    # span 1024 over 512 buckets: v = t / 2 is exact, so every even
    # stamp lands exactly on a bucket edge
    return np.repeat(np.arange(1025.0), 2), 512, 512


def _day(max_range, days=1, epoch=0.0, width=None):
    def make():
        rng = np.random.default_rng(max_range * days)
        t = epoch + np.sort(rng.uniform(0, days * 86_400.0, 40_000))
        return t, max_range * days, width or max_range * days
    return make


class TestBucketStarts:
    @pytest.mark.parametrize("case", [
        _edge_ties, _on_integers,
        lambda: (np.array([7.5]), 600, 600),               # n = 1
        lambda: (np.full(9, 1.5e9), 600, 600),             # span == 0
        lambda: (np.array([0.0, 86_400.0]), 600, 600),     # n = 2
        _day(600, width=3600),                             # width > range
        _day(3600, epoch=1.5e9),                           # epoch seconds
        *[_day(r) for r in (600, 1200, 1800, 2400, 3000, 3600)],
        *[_day(r, days=2, epoch=1.5e9)
          for r in (600, 1200, 1800, 2400, 3000, 3600)],
    ], ids=["edge_ties", "on_integers", "n1", "span0", "n2",
            "width_gt_range", "epoch"]
        + [f"grid{r}" for r in (600, 1200, 1800, 2400, 3000, 3600)]
        + [f"stream{2 * r}" for r in (600, 1200, 1800, 2400, 3000, 3600)])
    def test_bisection_matches_searchsorted(self, case):
        t, max_range, width = case()
        starts, counts, _, _ = ops._nsa_tables(t, max_range, 3.0, width)
        want = _starts_by_searchsorted(t, max_range, width)
        np.testing.assert_array_equal(starts, want)
        assert counts.sum() == len(t)


class TestStreamSampleBatched:
    @pytest.mark.parametrize("lengths", [
        (256, 256, 256),          # uniform
        (100, 5000, 1237),        # ragged + unaligned tails
        (TILE, 1, 3 * TILE + 7),  # single-record stream + exact tile
    ])
    def test_batched_equals_looped(self, lengths):
        # one 2-D-grid dispatch == S sequential single-stream dispatches
        mr = 60
        ts = [_sorted_times(n, 86_400.0, seed=90 + i) if n > 1
              else np.array([float(i)]) for i, n in enumerate(lengths)]
        mults = [86_400.0 / mr * (1 + 0.5 * i) for i in range(len(ts))]
        ss_b, keep_b, lens = ops.stream_sample_batched(ts, mr, mults)
        for s, t in enumerate(ts):
            ss_1, keep_1 = ops.stream_sample(t, mr, mults[s])
            n = lens[s]
            np.testing.assert_array_equal(np.asarray(ss_b[s, :n]),
                                          np.asarray(ss_1))
            np.testing.assert_array_equal(np.asarray(keep_b[s, :n]),
                                          np.asarray(keep_1))
            assert not np.asarray(keep_b[s, n:]).any(), "padded tail kept"

    def test_scalar_multiple_broadcasts(self):
        ts = [_sorted_times(500, 3600.0, seed=5) for _ in range(2)]
        ss_b, keep_b, _ = ops.stream_sample_batched(ts, 30, 120.0)
        assert ss_b.shape == keep_b.shape == (2, TILE)

    def test_empty_stream_rejected(self):
        with pytest.raises(ValueError):
            ops.stream_sample_batched([np.zeros(0)], 10, 1.0)

    def test_range_padded_kernel_matches_ref(self):
        # rows at different max_range in one launch, tables padded to the
        # widest: the kernel against its pure-jnp oracle, bit for bit
        from repro.kernels.stream_sample import stream_sample_pallas
        rng = np.random.default_rng(29)
        ts = [np.sort(rng.uniform(0, 900.0, 2 * TILE)) for _ in range(2)]
        width = 600
        tables = [ops._nsa_tables(t, r, 3.0, width)
                  for t, r in zip(ts, (600, 240))]
        args = [jnp.asarray(np.stack([ops._rebase(t) for t in ts]))] + \
            [jnp.asarray(np.stack(col)) for col in zip(*tables)]
        args[4] = args[4].astype(jnp.float32)
        ss_k, keep_k = stream_sample_pallas(*args, width, interpret=True)
        ss_r, keep_r = ref.stream_sample_ref(*args, width)
        np.testing.assert_array_equal(np.asarray(ss_k), np.asarray(ss_r))
        np.testing.assert_array_equal(np.asarray(keep_k), np.asarray(keep_r))

    @pytest.mark.parametrize("path", ["batched", "chunked"])
    def test_rows_sharing_an_array_match_copies(self, path):
        # rows holding one array object share its rebase and upload; rows
        # holding copies take one each — every output must agree
        from repro import obs
        from repro.streamsim.nsa import ChunkedNSA
        from repro.streamsim.preprocess import Stream
        a = _sorted_times(3000, 86_400.0, seed=11) + 1.5e9
        b = _sorted_times(700, 86_400.0, seed=12)
        rows = [(a, 60), (a, 120), (b, 60), (a, 240), (b, 240)]

        def run(arrays):
            with obs.recording() as rec:
                if path == "batched":
                    out = ops.stream_sample_batched(
                        arrays, [r for _, r in rows], 3.0)
                    out = [np.asarray(x) for x in out]
                else:
                    streams = {str(i): Stream(str(i), t, {})
                               for i, t in enumerate(arrays)}
                    sweep = ChunkedNSA(streams, [
                        (str(i), r) for i, (_, r) in enumerate(rows)])
                    out = []
                    for lo in range(0, sweep.width, 50):
                        h = sweep.chunk(lo, min(lo + 50, sweep.width))
                        out += [np.asarray(h.ss_kept), np.asarray(h.idx),
                                np.asarray(h.totals), h.rec_off]
            counts = rec.counts()
            return out, (counts["nsa.tables_rows"],
                         counts["nsa.tables_datasets"])

        shared, n_shared = run([t for t, _ in rows])
        copied, n_copied = run([np.copy(t) for t, _ in rows])
        assert n_shared == (5, 2)
        assert n_copied == (5, 5)
        assert len(shared) == len(copied)
        for got, want in zip(shared, copied):
            np.testing.assert_array_equal(got, want)
        if path == "batched":
            for s, (t, r) in enumerate(rows):
                ss_1, keep_1 = ops.stream_sample(t, r, 3.0)
                np.testing.assert_array_equal(shared[0][s, :len(t)], ss_1)
                np.testing.assert_array_equal(shared[1][s, :len(t)], keep_1)


class TestCompact:
    @pytest.mark.parametrize("n", [1, 100, TILE - 1, TILE, TILE + 1,
                                   4 * TILE, 10_000])
    @pytest.mark.parametrize("density", [0.0, 0.3, 0.5, 1.0])
    def test_matches_oracle_and_nonzero(self, n, density):
        rng = np.random.default_rng(n + int(density * 7))
        mask = (rng.random(n) < density)
        if density == 1.0:
            mask[:] = True          # all kept
        idx, total = ops.compact_mask(mask)
        exp = np.flatnonzero(mask)
        assert total == len(exp)
        np.testing.assert_array_equal(np.asarray(idx[:total]), exp)
        assert np.all(np.asarray(idx[total:]) == n), "sentinel tail"
        # positions agree with the pure-jnp oracle
        from repro.kernels.compact import compact_positions_pallas
        pad = (-n) % TILE
        mp = jnp.asarray(np.concatenate([mask, np.zeros(pad, bool)]),
                         jnp.int32)
        pos_k, tot_k = compact_positions_pallas(mp, interpret=True)
        pos_o, tot_o = ref.compact_ref(mp)
        np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_o))
        assert int(tot_k[0]) == int(tot_o[0]) == total

    def test_bool_and_int_masks(self):
        m = np.array([1, 0, 1, 1, 0], np.int64)
        idx_i, tot_i = ops.compact_mask(m)
        idx_b, tot_b = ops.compact_mask(m.astype(bool))
        assert tot_i == tot_b == 3
        np.testing.assert_array_equal(np.asarray(idx_i), np.asarray(idx_b))

    def test_empty(self):
        idx, total = ops.compact_mask(np.zeros(0, bool))
        assert total == 0 and idx.shape == (0,)


class TestCompactBatched:
    """R rows' mask compactions in ONE 2-D-grid dispatch (per-row SMEM
    carry reset) must be bit-identical to R sequential compactions."""

    @pytest.mark.parametrize("shape,densities", [
        ((1, 100), (0.3,)),
        ((3, TILE), (0.0, 0.5, 1.0)),          # empty / mixed / all-kept rows
        ((4, 10_000), (0.1, 0.9, 0.0, 0.5)),   # unaligned record tail
    ])
    def test_batched_equals_looped(self, shape, densities):
        R, n = shape
        rng = np.random.default_rng(R * n)
        mask = np.stack([rng.random(n) < d for d in densities])
        idx_b, totals = ops.compact_mask_batched_device(mask)
        totals = np.asarray(totals)
        assert idx_b.shape == (R, n) and totals.shape == (R,)
        for r in range(R):
            idx_1, total_1 = ops.compact_mask(mask[r])
            assert totals[r] == total_1
            np.testing.assert_array_equal(np.asarray(idx_b[r]),
                                          np.asarray(idx_1))
            exp = np.flatnonzero(mask[r])
            np.testing.assert_array_equal(np.asarray(idx_b[r, :totals[r]]),
                                          exp)
            assert np.all(np.asarray(idx_b[r, totals[r]:]) == n), \
                "sentinel tail"

    def test_kernel_positions_match_cumsum(self):
        from repro.kernels.compact import compact_positions_batched_pallas
        mask = (np.random.default_rng(29).random((5, 2 * TILE)) < 0.35) \
            .astype(np.int32)
        pos, tot = compact_positions_batched_pallas(jnp.asarray(mask),
                                                    interpret=True)
        incl = np.cumsum(mask, axis=1)
        np.testing.assert_array_equal(np.asarray(pos), incl - mask)
        np.testing.assert_array_equal(np.asarray(tot).ravel(), incl[:, -1])

    def test_carry_resets_between_rows(self):
        # identical all-kept rows: a leaking carry would shift row 1's
        # positions by row 0's total
        mask = np.ones((2, 2 * TILE), bool)
        idx_b, totals = ops.compact_mask_batched_device(mask)
        np.testing.assert_array_equal(totals, [2 * TILE, 2 * TILE])
        np.testing.assert_array_equal(np.asarray(idx_b[0]),
                                      np.asarray(idx_b[1]))

    def test_empty_and_bad_shapes(self):
        idx, totals = ops.compact_mask_batched_device(np.zeros((2, 0), bool))
        assert idx.shape == (2, 0) and list(np.asarray(totals)) == [0, 0]
        with pytest.raises(ValueError):
            ops.compact_mask_batched_device(np.zeros(5, bool))


class TestStreamMetrics:
    """The fused metrics engine: histogram + moments in one record pass."""

    @pytest.mark.parametrize("n,max_range", [(1, 16), (512, 16), (4096, 128),
                                             (20_000, 600), (1024, 3600),
                                             (4096, 86_400), (TILE - 1, 600),
                                             (TILE + 1, 600)])
    def test_matches_oracle(self, n, max_range):
        rng = np.random.default_rng(n + max_range)
        ss = np.sort(rng.integers(0, max_range, n)).astype(np.int32)
        h_k, m_k = ops.stream_metrics(ss, max_range)
        h_o = ref.bucket_hist_ref(jnp.asarray(ss), max_range)
        np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_o))
        assert h_k.dtype == jnp.int32, "int32 counts — no f32 rounding"
        assert int(h_k.sum()) == n
        q = np.asarray(h_o, np.float64)
        np.testing.assert_allclose(np.asarray(m_k),
                                   [q.sum(), (q * q).sum()], rtol=1e-5)

    def test_kernel_batched_matches_ref(self):
        # several rows in one dispatch: counts bit-exact, moments within
        # the Kahan fold's 1e-5 of the reference
        from repro.kernels.metrics_fused import stream_metrics_pallas
        ss = np.sort(np.random.default_rng(29).integers(0, 1500, (4, 2048)),
                     axis=1).astype(np.int32)
        h_k, m_k = stream_metrics_pallas(jnp.asarray(ss), 1536,
                                         interpret=True)
        h_r, m_r = ref.stream_metrics_ref(jnp.asarray(ss), 1536)
        np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
        np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                                   rtol=1e-5, atol=1e-2)

    def test_kernel_padding_ids_count_nowhere(self):
        from repro.kernels.metrics_fused import stream_metrics_pallas
        ss = np.full((2, TILE), 10_000, np.int32)        # all padding ids
        ss[0, :5] = [0, 1, 1, 2, 511]
        h, m = stream_metrics_pallas(jnp.asarray(ss), 512, interpret=True)
        h = np.asarray(h)
        assert h[0].sum() == 5 and h[1].sum() == 0
        assert h[0][1] == 2
        np.testing.assert_array_equal(np.asarray(m)[1], [0.0, 0.0])

    def test_carry_kernel_composes_across_chunks(self):
        # zero carry-in: the plain kernel's output, bit for bit; two
        # chained chunks: the running [Σq, Σq²] of both histograms
        from repro.kernels.metrics_fused import (stream_metrics_carry_pallas,
                                                 stream_metrics_pallas)
        rng = np.random.default_rng(29)
        a, b = (np.sort(rng.integers(0, 1024, (3, TILE)), axis=1)
                .astype(np.int32) for _ in range(2))
        zero = jnp.zeros((3, 4), jnp.float32)
        h1, c1 = stream_metrics_carry_pallas(jnp.asarray(a), zero, 1024,
                                             interpret=True)
        h2, c2 = stream_metrics_carry_pallas(jnp.asarray(b), c1, 1024,
                                             interpret=True)
        h0, m0 = stream_metrics_pallas(jnp.asarray(a), 1024, interpret=True)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h0))
        np.testing.assert_array_equal(np.asarray(c1)[:, ::2],
                                      np.asarray(m0))
        for h, x in ((h1, a), (h2, b)):
            np.testing.assert_array_equal(
                np.asarray(h), [np.bincount(r, minlength=1024) for r in x])
        q = np.concatenate([np.asarray(h1), np.asarray(h2)], axis=1) \
            .astype(np.float64)
        np.testing.assert_allclose(
            np.asarray(c2)[:, ::2],
            np.stack([q.sum(axis=1), (q * q).sum(axis=1)], axis=1),
            rtol=1e-6)

    def test_unsorted_input_still_exact(self):
        # sortedness only narrows the kernel's data-adaptive bucket-block
        # loop; correctness must not depend on it
        rng = np.random.default_rng(7)
        ss = rng.integers(0, 600, 5000).astype(np.int32)
        h_k, _ = ops.stream_metrics(ss, 600)
        np.testing.assert_array_equal(np.asarray(h_k),
                                      np.bincount(ss, minlength=600))

    @pytest.mark.parametrize("lengths", [(256, 256), (0, 1, 3000, 1024),
                                         (1, 8192)])
    def test_batched_equals_looped(self, lengths):
        rng = np.random.default_rng(sum(lengths))
        mr = 300
        sss = [np.sort(rng.integers(0, mr, n)).astype(np.int32)
               for n in lengths]
        h_b, m_b, lens = ops.stream_metrics_batched(sss, mr)
        np.testing.assert_array_equal(lens, lengths)
        for s, ss in enumerate(sss):
            np.testing.assert_array_equal(
                np.asarray(h_b[s]), np.bincount(ss, minlength=mr))
            if len(ss) == 0:
                assert float(m_b[s, 0]) == float(m_b[s, 1]) == 0.0
            else:
                h_1, m_1 = ops.stream_metrics(ss, mr)
                np.testing.assert_array_equal(np.asarray(h_b[s]),
                                              np.asarray(h_1))
                np.testing.assert_allclose(np.asarray(m_b[s]),
                                           np.asarray(m_1), rtol=1e-6)

    def test_out_of_range_stamps_rejected(self):
        with pytest.raises(ValueError):
            ops.stream_metrics(np.array([0, 600]), 600)
        with pytest.raises(ValueError):
            ops.stream_metrics(np.array([-1, 5]), 600)

    def test_moments_tight_on_day_scale(self):
        # pairwise-block + Kahan summation in the kernel: the [Σq, Σq²]
        # pair must agree with exact f64 within 1e-5 relative on the
        # day-scale fixture (86 400 buckets) — an order tighter than the
        # 1e-3 the naive running f32 sum guaranteed
        rng = np.random.default_rng(42)
        ss = np.sort(rng.integers(0, 86_400, 1_000_000)).astype(np.int32)
        hist, mom = ops.stream_metrics(ss, 86_400)
        q = np.asarray(hist, np.float64)
        np.testing.assert_allclose(np.asarray(mom, np.float64),
                                   [q.sum(), (q * q).sum()], rtol=1e-5)

    def test_int32_overflow_domain_guarded(self):
        # counts accumulate in int32: exact up to 2**31 per bucket (the
        # seed's f32 one-hot kernel silently rounded past 2**24); beyond
        # the int32 domain the wrapper must raise, not wrap
        ops._check_metrics_domain(2 ** 31 - 1)  # in-domain: no raise
        with pytest.raises(ops.PallasDomainError):
            ops._check_metrics_domain(2 ** 31)

    def test_empty_batch_rejected(self):
        with pytest.raises(ValueError):
            ops.stream_metrics_batched([], 10)


class TestBucketHist:
    @pytest.mark.parametrize("n,max_range", [(512, 16), (4096, 128),
                                             (20_000, 600), (1024, 3600),
                                             (TILE - 1, 128),
                                             (TILE + 1, 3600)])
    def test_matches_oracle(self, n, max_range):
        rng = np.random.default_rng(n)
        ss = np.sort(rng.integers(0, max_range, n)).astype(np.int32)
        h_k = ops.bucket_hist(ss, max_range)
        h_o = ref.bucket_hist_ref(jnp.asarray(ss), max_range)
        np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_o))
        assert int(h_k.sum()) == n


class TestVolatility:
    @pytest.mark.parametrize("n", [60, 600, 3600, 86_400])
    def test_moments(self, n):
        rng = np.random.default_rng(n)
        q = rng.poisson(25.0, n).astype(np.float32)
        avg, var, std = ops.volatility_stats(q)
        assert np.isclose(float(avg), q.mean(), rtol=1e-5)
        assert np.isclose(float(var), q.var(), rtol=1e-4)
        assert np.isclose(float(std), q.std(), rtol=1e-4)

    def test_against_ref(self):
        q = np.arange(1024, dtype=np.float32)
        s, s2 = ops.volatility_moments(q)
        exp = ref.volatility_ref(jnp.asarray(q))
        assert np.isclose(float(s), float(exp[0]))
        assert np.isclose(float(s2), float(exp[1]), rtol=1e-6)


class TestFlashDecode:
    @pytest.mark.parametrize("b,h,kh,d,s", [
        (1, 4, 4, 32, 256),     # MHA
        (2, 8, 2, 64, 512),     # GQA 4:1
        (4, 16, 1, 64, 1024),   # MQA
        (2, 12, 4, 128, 384),   # uneven block tail
    ])
    def test_matches_oracle(self, b, h, kh, d, s):
        key = jax.random.PRNGKey(b * 100 + s)
        q = jax.random.normal(key, (b, h, d), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kh, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kh, d))
        lens = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1, s + 1)
        out = ops.flash_decode(q, k, v, lens, block_s=128)
        exp = ref.flash_decode_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        key = jax.random.PRNGKey(0)
        b, h, kh, d, s = 2, 8, 4, 64, 256
        q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kh, d),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kh, d),
                              jnp.bfloat16)
        lens = jnp.full((b,), s, jnp.int32)
        out = ops.flash_decode(q, k, v, lens, block_s=128)
        exp = ref.flash_decode_ref(q, k, v, lens)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            rtol=5e-2, atol=5e-2)

    def test_prefix_only_attention(self):
        """Tokens beyond `lengths` must not influence the output."""
        key = jax.random.PRNGKey(7)
        b, h, kh, d, s = 2, 4, 2, 32, 256
        q = jax.random.normal(key, (b, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kh, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kh, d))
        lens = jnp.array([100, 40], jnp.int32)
        out1 = ops.flash_decode(q, k, v, lens, block_s=64)
        k2 = k.at[:, 150:].set(999.0)
        v2 = v.at[:, 150:].set(-999.0)
        out2 = ops.flash_decode(q, k2, v2, lens, block_s=64)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6)
