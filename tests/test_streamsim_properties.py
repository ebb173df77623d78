"""Property-based NSA + stream-task tests (hypothesis). Skipped wholesale
when hypothesis is not installed (``pip install -r requirements-dev.txt``);
the deterministic suites in ``test_streamsim.py``/``test_tasks.py`` run
regardless."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from repro.streamsim import nsa, nsa_paper
from repro.streamsim.nsa import systematic_keep_mask
from repro.streamsim.preprocess import Stream
from repro.streamsim.queue import Bucket, StreamQueue
from repro.streamsim.tasks import EventDetectTask, WindowedStatsTask


@st.composite
def sorted_timestamps(draw):
    n = draw(st.integers(min_value=2, max_value=400))
    deltas = draw(st.lists(st.floats(0.0, 50.0, allow_nan=False),
                           min_size=n, max_size=n))
    t0 = draw(st.floats(0, 1e9, allow_nan=False))
    t = np.cumsum(np.asarray(deltas, np.float64)) + t0
    return t


class TestNSAProperties:
    @settings(max_examples=60, deadline=None)
    @given(t=sorted_timestamps(), max_range=st.integers(2, 200))
    def test_invariants(self, t, max_range):
        s = Stream("h", t, {"x": np.arange(len(t))})
        d = nsa(s, max_range)
        # 1. output is a subsequence (order + subset)
        assert np.all(np.diff(d.t) >= 0)
        xs = d.payload["x"]
        assert np.all(np.diff(xs) > 0)
        # 2. scale stamps bounded + non-decreasing
        if len(d):
            assert d.scale_stamp.min() >= 0
            assert d.scale_stamp.max() < max_range
            assert np.all(np.diff(d.scale_stamp) >= 0)
        # 3. never drops everything, never grows
        assert 1 <= len(d) <= len(s)
        # 4. deterministic
        d2 = nsa(s, max_range)
        assert np.array_equal(d.t, d2.t)

    @settings(max_examples=30, deadline=None)
    @given(t=sorted_timestamps(), max_range=st.integers(2, 100))
    def test_paper_loop_agrees(self, t, max_range):
        s = Stream("h", t, {"x": np.arange(len(t))})
        a, b = nsa(s, max_range), nsa_paper(s, max_range)
        assert np.array_equal(a.t, b.t)

    @settings(max_examples=10, deadline=None)
    @given(t=sorted_timestamps(), max_range=st.sampled_from([3, 60, 600]))
    def test_pallas_backend_agrees(self, t, max_range):
        s = Stream("h", t, {"x": np.arange(len(t))})
        a = nsa(s, max_range, backend="pallas")
        b = nsa(s, max_range, backend="numpy")
        assert np.array_equal(a.t, b.t)
        assert np.array_equal(a.scale_stamp, b.scale_stamp)

    @settings(max_examples=30, deadline=None)
    @given(counts=st.lists(st.integers(0, 50), min_size=1, max_size=60),
           mult=st.floats(1.0, 40.0))
    def test_keep_mask_counts(self, counts, mult):
        # per bucket with c records, exactly clip(round(c/mult),1) survive
        ss = np.repeat(np.arange(len(counts)), counts)
        mask = systematic_keep_mask(ss, len(counts), mult)
        kept = np.bincount(ss[mask], minlength=len(counts))
        for b, c in enumerate(counts):
            if c:
                assert kept[b] == max(int(round(c / mult)), 1)
            else:
                assert kept[b] == 0


# ------------------------------------------------------- stream-task tier
def _bucket(stamp, count):
    return Bucket(scale_stamp=int(stamp),
                  t=np.full(int(count), float(stamp)),
                  payload={"v": np.ones(int(count))}, emit_time=0.0)


def _queue_of(buckets):
    q = StreamQueue(maxsize=max(len(buckets), 1))
    for b in buckets:
        q.put(b)
    q.close()
    return q


def _tumbling_oracle(q, w):
    """O(n*w) literal tumbling mean (true-length trailing window)."""
    return np.array([np.mean(q[i:i + w]) for i in range(0, len(q), w)])


def _sliding_oracle(q, w):
    """O(n*w) literal sliding mean — constant 1/w weight, zero-padded
    edges, window [i - (w - half - 1), i + half] (the convolve
    mode=\"same\" convention sliding_mean promises; for even windows the
    extra element sits on the LEFT)."""
    n = len(q)
    w = max(min(w, n), 1)
    half = (w - 1) // 2
    out = np.empty(n)
    for i in range(n):
        lo, hi = i + half + 1 - w, i + half + 1
        out[i] = q[max(lo, 0):min(hi, n)].sum() / w
    return out


class TestWindowedStatsProperties:
    @settings(max_examples=50, deadline=None)
    @given(counts=st.lists(st.integers(0, 40), min_size=1, max_size=200),
           window=st.integers(1, 50))
    def test_sliding_vs_quadratic_oracle(self, counts, window):
        q = np.asarray(counts, np.float64)
        task = WindowedStatsTask(window_s=window)
        np.testing.assert_allclose(task.aggregate(q),
                                   _sliding_oracle(q, window), atol=1e-9)

    @settings(max_examples=50, deadline=None)
    @given(counts=st.lists(st.integers(0, 40), min_size=1, max_size=200),
           window=st.integers(1, 50))
    def test_tumbling_vs_quadratic_oracle(self, counts, window):
        q = np.asarray(counts, np.float64)
        task = WindowedStatsTask(window_s=window, mode="tumbling")
        np.testing.assert_allclose(task.aggregate(q),
                                   _tumbling_oracle(q, window), atol=1e-9)


@st.composite
def reordered_buckets(draw):
    """(in-order buckets, reordered buckets, window): a bucket-preserving
    reorder displacing every bucket < window positions (the fault layer's
    bounded-reorder contract)."""
    counts = draw(st.lists(st.integers(0, 12), min_size=2, max_size=120))
    window = draw(st.integers(1, 10))
    buckets = [_bucket(i, c) for i, c in enumerate(counts)]
    shuffled = []
    for i in range(0, len(buckets), window):
        block = list(buckets[i:i + window])
        perm = draw(st.permutations(range(len(block))))
        shuffled.extend(block[j] for j in perm)
    return buckets, shuffled, window


class TestEventDetectProperties:
    @settings(max_examples=40, deadline=None)
    @given(data=reordered_buckets(), drift=st.floats(0.0, 2.0),
           h=st.floats(0.5, 10.0))
    def test_cusum_invariant_with_watermark(self, data, drift, h):
        """CUSUM detection with reorder_tolerance >= the reorder window is
        INVARIANT under any bucket-preserving reorder inside that window:
        the watermark heap re-sorts a w-displaced arrival sequence
        exactly."""
        ordered, shuffled, window = data
        kw = dict(mode="cusum", drift=drift, h=h,
                  reorder_tolerance=window)
        a = EventDetectTask(**kw)(_queue_of(ordered))
        b = EventDetectTask(**kw)(_queue_of(shuffled))
        assert a["task_events"].tolist() == b["task_events"].tolist()
        assert a["detect_events"] == b["detect_events"]

    @settings(max_examples=40, deadline=None)
    @given(data=reordered_buckets(), threshold=st.floats(0.0, 12.0))
    def test_threshold_event_set_invariant(self, data, threshold):
        """Threshold detection stamps events with the triggering bucket's
        own scale stamp, so the event SET survives ANY reorder even with
        no watermark buffer."""
        ordered, shuffled, _ = data
        a = EventDetectTask(mode="threshold",
                            threshold=threshold)(_queue_of(ordered))
        b = EventDetectTask(mode="threshold",
                            threshold=threshold)(_queue_of(shuffled))
        assert sorted(a["task_events"]) == sorted(b["task_events"])

