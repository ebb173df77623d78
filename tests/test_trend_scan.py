"""Device-resident trend & S×S correlation engine tests.

Contract under test (see kernels/trend_scan.py + streamsim/metrics.py):
the prefix-sum scan kernel is bit-exact against its cumsum oracle; the
trend produced from it matches the host cumsum sliding mean within 1e-3
(window sums int32-exact, divide f32); the S×S correlation matrix is
symmetric with a unit diagonal and agrees with the float64 numpy mirror
within 1e-3; out-of-domain inputs raise PallasDomainError at the ops
layer and fall back to numpy in the metrics layer.
"""

import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.trend_scan import (PAIR_TILE, TILE, pair_stats_pallas,
                                      trend_scan_carry_pallas,
                                      trend_scan_pallas)
from repro.streamsim import Controller, trend_correlation_matrix
from repro.streamsim.metrics import (_corr_matrix_numpy, sliding_mean,
                                     trend_correlation_from_counts)


def _counts(n, seed=0, lam=25.0):
    return np.random.default_rng(seed).poisson(lam, n).astype(np.int64)


class TestScanKernel:
    @pytest.mark.parametrize("S,tiles", [(1, 1), (1, 3), (4, 2), (7, 1),
                                         (6, 2)])
    def test_prefix_sum_bit_exact_vs_oracle(self, S, tiles):
        rng = np.random.default_rng(S * 10 + tiles)
        q = rng.integers(0, 1000, (S, tiles * TILE)).astype(np.int32)
        import jax.numpy as jnp
        got = np.asarray(trend_scan_pallas(jnp.asarray(q), interpret=True))
        exp = np.asarray(ref.trend_scan_ref(jnp.asarray(q)))
        np.testing.assert_array_equal(got, exp)
        # and both equal the int64 host cumsum (no int32 wrap at this scale)
        np.testing.assert_array_equal(got, np.cumsum(q, axis=1))

    def test_carry_resets_between_streams(self):
        # stream 1's scan must not inherit stream 0's carry
        import jax.numpy as jnp
        q = np.ones((2, 2 * TILE), np.int32)
        got = np.asarray(trend_scan_pallas(jnp.asarray(q), interpret=True))
        np.testing.assert_array_equal(got[1], np.arange(1, 2 * TILE + 1))

    def test_carry_in_seeds_the_scan(self):
        # each row's scan starts from its carry-in; the tail is the new
        # running total, the next chunk's carry-in
        import jax.numpy as jnp
        rng = np.random.default_rng(29)
        q = rng.integers(0, 9, (4, TILE)).astype(np.int32)
        init = rng.integers(0, 1000, 4).astype(np.int32)
        psum, tail = trend_scan_carry_pallas(jnp.asarray(q),
                                             jnp.asarray(init),
                                             interpret=True)
        exp = init[:, None] + np.cumsum(q, axis=1)
        np.testing.assert_array_equal(np.asarray(psum), exp)
        np.testing.assert_array_equal(np.asarray(tail), exp[:, -1])

    @pytest.mark.parametrize("S,k_tiles", [(1, 1), (2, 2), (5, 3), (5, 4)])
    def test_pair_stats_vs_oracle(self, S, k_tiles):
        rng = np.random.default_rng(S + k_tiles)
        x = rng.normal(0, 3, (S, k_tiles * PAIR_TILE)).astype(np.float32)
        import jax.numpy as jnp
        sums, gram = pair_stats_pallas(jnp.asarray(x), interpret=True)
        sums_r, gram_r = ref.pair_stats_ref(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gram), np.asarray(gram_r),
                                   rtol=1e-4, atol=1e-3)


class TestTrendScanOps:
    @pytest.mark.parametrize("n,w", [(0, 5), (1, 1), (1, 600), (10, 1),
                                     (10, 4), (100, 600), (7, 7), (2, 5),
                                     (5000, 60)])
    def test_matches_host_sliding_mean(self, n, w):
        q = _counts(n, seed=n * 100 + w)
        got = np.asarray(ops.trend_scan(q, w))
        exp = sliding_mean(q.astype(np.float64), w)
        assert got.shape == exp.shape
        np.testing.assert_allclose(got, exp, rtol=1e-3, atol=1e-5)

    def test_ragged_batch_equals_per_stream(self):
        qs = [_counts(n, seed=n) for n in (0, 1, 17, 600, 3600)]
        trend_b, lengths = ops.trend_scan_batched(qs, 60)
        trend_b = np.asarray(trend_b)
        np.testing.assert_array_equal(lengths, [len(q) for q in qs])
        for s, q in enumerate(qs):
            np.testing.assert_allclose(
                trend_b[s, :len(q)],
                sliding_mean(q.astype(np.float64), 60),
                rtol=1e-3, atol=1e-5)
            # padded tail stays zero
            assert not trend_b[s, len(q):].any()

    def test_bad_window_rejected(self):
        with pytest.raises(ValueError):
            ops.trend_scan(_counts(10), 0)

    def test_negative_counts_are_a_domain_violation(self):
        # PallasDomainError (not a plain ValueError) so the metrics layer
        # falls back to numpy instead of diverging between backends
        with pytest.raises(ops.PallasDomainError):
            ops.trend_scan(np.array([1, -2, 3]), 2)
        qs = [np.array([5, -3, 2, 1]), np.array([1, 2, 3, 4])]
        np.testing.assert_array_equal(
            trend_correlation_matrix(qs, 2, backend="pallas"),
            trend_correlation_matrix(qs, 2, backend="numpy"))

    def test_domain_guard_raises(self):
        # total past 2**31 would wrap the int32 prefix sum -> refuse
        with pytest.raises(ops.PallasDomainError):
            ops.trend_scan(np.array([2 ** 31 - 1, 5], np.int64), 3)


class TestCorrelationMatrix:
    def _qs(self):
        base = _counts(3600, seed=1)
        phase = np.roll(base, 600)
        noise = _counts(1200, seed=2)
        return [base, phase, noise]

    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    def test_symmetry_and_unit_diagonal(self, backend):
        m = trend_correlation_matrix(self._qs(), 60, backend=backend)
        assert m.shape == (3, 3)
        np.testing.assert_array_equal(m, m.T)
        np.testing.assert_array_equal(np.diag(m), np.ones(3))
        assert (np.abs(m) <= 1.0).all()

    def test_backends_agree_within_tolerance(self):
        mn = trend_correlation_matrix(self._qs(), 60, backend="numpy")
        mp = trend_correlation_matrix(self._qs(), 60, backend="pallas")
        np.testing.assert_allclose(mn, mp, atol=1e-3)

    def test_pair_entry_matches_pairwise_host_convention(self):
        # with the default grid (shortest series) and S = 2 the matrix
        # reproduces trend_correlation_from_counts
        qa, qb = _counts(3600, seed=3), _counts(900, seed=4)
        host = trend_correlation_from_counts(qa, qb, 60)
        for backend in ("numpy", "pallas"):
            m = trend_correlation_matrix([qa, qb], 60, backend=backend)
            assert m[0, 1] == pytest.approx(host, abs=1e-3)

    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    def test_empty_and_zero_variance_rows_are_nan(self, backend):
        # empty series + all-zero counts (the zero-padded "same"-mode edges
        # give a CONSTANT series a ramping trend, so only all-zero counts
        # have truly zero trend variance)
        qs = [_counts(600, seed=5), np.zeros(0, np.int64),
              np.zeros(300, np.int64)]
        m = trend_correlation_matrix(qs, 60, backend=backend)
        assert np.isnan(m[1]).all() and np.isnan(m[:, 1]).all()
        assert np.isnan(m[2]).all() and np.isnan(m[:, 2]).all()
        assert m[0, 0] == 1.0

    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    def test_n_points_override(self, backend):
        qs = [_counts(3600, seed=6), _counts(1800, seed=7)]
        m = trend_correlation_matrix(qs, 60, n_points=256, backend=backend)
        ref_m = _corr_matrix_numpy([np.asarray(q) for q in qs], 60, 256)
        np.testing.assert_allclose(m, ref_m, atol=1e-3)

    def test_bad_window_rejected(self):
        with pytest.raises(ValueError):
            trend_correlation_matrix([_counts(10)], 0)

    def test_out_of_domain_falls_back_to_numpy(self):
        # totals past the int32 scan domain must produce the numpy answer,
        # not an error and not a silently wrong device result
        qs = [np.array([2 ** 31 - 1, 5, 9], np.int64), _counts(3, seed=8)]
        m = trend_correlation_matrix(qs, 2, backend="pallas")
        np.testing.assert_array_equal(
            m, trend_correlation_matrix(qs, 2, backend="numpy"))

    def test_pallas_path_never_runs_host_cumsum(self, monkeypatch):
        # the acceptance criterion: no host cumsum / per-pair loop in the
        # device path — sliding_mean (the host cumsum) must never fire
        import repro.streamsim.metrics as metrics

        def _boom(*a, **k):
            raise AssertionError("host sliding_mean used in pallas path")

        monkeypatch.setattr(metrics, "sliding_mean", _boom)
        m = trend_correlation_matrix(self._qs(), 60, backend="pallas")
        assert np.isfinite(m).all()
        with pytest.raises(AssertionError):
            trend_correlation_matrix(self._qs(), 60, backend="numpy")


class TestTrendFallback:
    def test_trend_falls_back_when_ops_rejects(self, monkeypatch):
        from repro.streamsim import make_stream, preprocess
        from repro.streamsim.metrics import trend

        def _reject(*a, **k):
            raise ops.PallasDomainError("forced for test")

        monkeypatch.setattr(ops, "trend_scan", _reject)
        s = preprocess(make_stream("traffic", scale=0.005, seed=2))
        np.testing.assert_allclose(trend(s, 60, backend="pallas"),
                                   trend(s, 60, backend="numpy"),
                                   rtol=1e-12)


class TestRunManyFidelity:
    @staticmethod
    def _consumer(queue):
        return {"records_seen": sum(len(b) for b in queue)}

    def test_one_matrix_dispatch_per_sweep(self, tmp_path, monkeypatch):
        # S×S fidelity comes from ONE batched matrix call per max_range —
        # not a per-pair (or per-scenario) host loop (the matrix call
        # lives in the engine's report layer since the plan/engine split)
        import repro.streamsim.engine as engine

        calls = []
        real = engine.trend_correlation_matrix

        def _counting(counts, *a, **k):
            calls.append(len(counts))
            return real(counts, *a, **k)

        monkeypatch.setattr(engine, "trend_correlation_matrix",
                            _counting)
        datasets, max_ranges = ["traffic", "sogouq"], [40, 80]
        c = Controller(str(tmp_path / "fid"))
        reports = c.run_many(datasets, max_ranges, self._consumer,
                             scale=0.002, seed=9)
        assert len(reports) == len(datasets) * len(max_ranges)
        assert calls == [2 * len(datasets)] * len(max_ranges)

        assert len(c.last_fidelity) == len(max_ranges)
        for fr, mr in zip(c.last_fidelity, max_ranges):
            m = np.asarray(fr.trend_corr)
            S = 2 * len(datasets)
            assert fr.max_range == mr and m.shape == (S, S)
            assert fr.labels[:len(datasets)] == \
                [f"{d}/original" for d in datasets]
            np.testing.assert_array_equal(m, m.T)
            np.testing.assert_allclose(np.diag(m), 1.0)
        # persisted one JSON per sweep, outside list_metrics()'s glob
        assert len(c.list_fidelity()) == len(max_ranges)
        assert len(c.list_metrics()) == len(reports)
        loaded = c.load_fidelity()
        assert sorted(d["max_range"] for d in loaded) == sorted(max_ranges)

    def test_fidelity_json_is_strict(self, tmp_path):
        # NaN entries (empty / zero-variance streams) must serialize as
        # null — bare NaN tokens are not valid JSON
        import json

        from repro.streamsim.controller import FidelityReport

        c = Controller(str(tmp_path / "strict"))
        fr = FidelityReport(60, 60, ["a", "b"],
                            [[1.0, float("nan")], [float("nan"), 1.0]])
        path = c.save_fidelity(fr)

        def _no_constants(s):
            raise AssertionError(f"non-strict JSON token {s!r}")

        loaded = json.loads(path.read_text(), parse_constant=_no_constants)
        assert loaded["trend_corr"] == [[1.0, None], [None, 1.0]]
