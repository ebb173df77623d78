"""Spans and counters (``repro.obs``) and what ``run_many`` reports of them.

- a span's parent is the innermost span open on its thread; totals sum
  per name over parents; counters add; outside a recorder a span still
  times its block and a count is dropped; two threads under two
  recorders keep apart; compilations are counted;
- every report of a monolithic or chunked ``run_many`` call carries the
  call's spans and counters, with the top-level spans of its path;
- ``nsa_s``, ``produce_s`` and ``preprocess_s`` are the spans' seconds;
- ``store.bytes_written`` is the stored streams' bytes;
- no span takes the name of a benchmark window span (``sweep``,
  ``reset``), and every name is ``<layer>.<step>``;
- spans per sweep grow with the chunks, not with the records.
"""

import contextlib
import re
import threading

import pytest

from repro import obs
from repro.streamsim import Controller

MONOLITHIC_TOP = {"controller.prepare", "plan.sweep", "nsa.leg",
                  "engine.stats", "engine.materialize", "store.write",
                  "replay.loop", "engine.report"}
CHUNKED_TOP = {"controller.prepare", "plan.sweep", "chunk.prep",
               "replay.loop", "engine.stats", "engine.report"}


def _drain(queue):
    return {"consumed_records": sum(len(b) for b in queue)}


@pytest.fixture
def recorders(monkeypatch):
    """Every recorder ``run_many`` makes, kept for the test to read."""
    kept = []
    real = obs.recording

    @contextlib.contextmanager
    def keeping():
        with real() as rec:
            kept.append(rec)
            yield rec
    monkeypatch.setattr(obs, "recording", keeping)
    return kept


# ------------------------------------------------------------- the module
def test_nesting_parents_and_totals():
    with obs.recording() as rec:
        with obs.span("a.outer") as outer:
            for _ in range(2):
                with obs.span("b.inner") as inner:
                    pass
        with obs.span("b.inner"):
            pass
    assert rec.totals[("a.outer", None)].calls == 1
    assert rec.totals[("b.inner", "a.outer")].calls == 2
    assert rec.totals[("b.inner", None)].calls == 1
    assert outer.parent is None and inner.parent == "a.outer"
    assert rec.calls() == {"a.outer": 1, "b.inner": 3}
    assert rec.spans()["a.outer"] == outer.seconds
    assert outer.seconds >= inner.seconds >= 0.0
    assert rec.spans()["b.inner"] == pytest.approx(
        rec.totals[("b.inner", "a.outer")].seconds
        + rec.totals[("b.inner", None)].seconds)
    assert rec.top_level_s() == pytest.approx(
        outer.seconds + rec.totals[("b.inner", None)].seconds)
    assert rec.seconds >= rec.top_level_s()


def test_counters_add_and_nothing_records_outside_a_recorder():
    with obs.span("a.free") as free:
        obs.count("c.dropped", 5)
    assert free.seconds >= 0.0 and free.parent is None
    with obs.recording() as rec:
        obs.count("c.n", 3)
        obs.count("c.n")
    assert rec.counts() == {"c.n": 4}
    assert rec.spans() == {}


def test_a_recording_starts_its_own_parent_stack():
    with obs.span("a.outside"):
        with obs.recording() as rec:
            with obs.span("b.inside") as sp:
                pass
    assert sp.parent is None
    assert set(rec.totals) == {("b.inside", None)}


def test_two_threads_under_their_own_recorders_keep_apart():
    barrier = threading.Barrier(2, timeout=30)
    got = {}

    def work(tag, n):
        with obs.recording() as rec:
            for _ in range(n):
                barrier.wait()
                with obs.span(f"t.{tag}"):
                    obs.count("c.calls")
        got[tag] = rec

    threads = [threading.Thread(target=work, args=(tag, 5))
               for tag in ("x", "y")]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
    assert not any(th.is_alive() for th in threads)
    assert got["x"].calls() == {"t.x": 5}
    assert got["y"].calls() == {"t.y": 5}
    assert got["x"].counts() == got["y"].counts() == {"c.calls": 5}


def test_compilations_are_counted_on_the_compiling_thread():
    import jax
    import jax.numpy as jnp

    with obs.recording() as rec:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert rec.counts().get("jax.compiles", 0) >= 1


# ----------------------------------------------------------- run_many
def _sims(ctrl):
    return [k for k in ctrl.store.list() if "__sim" in k]


@pytest.fixture(scope="module")
def monolithic(tmp_path_factory):
    """A fresh store's first sweep and a second sweep after deleting the
    simulated streams (the benchmark's ``fresh`` mix)."""
    ctrl = Controller(str(tmp_path_factory.mktemp("mono") / "store"))
    kw = dict(scale=0.002, seed=3, backend="pallas")
    first = ctrl.run_many(["traffic", "sogouq"], [60, 600], _drain, **kw)
    written = sum(ctrl.store.get(k).nbytes() for k in ctrl.store.list())
    for k in _sims(ctrl):
        ctrl.store.delete(k)
    second = ctrl.run_many(["traffic", "sogouq"], [60, 600], _drain, **kw)
    return first, written, second


@pytest.fixture(scope="module")
def chunked(tmp_path_factory):
    ctrl = Controller(str(tmp_path_factory.mktemp("chunk") / "store"))
    reports = ctrl.run_many(["traffic"], [600, 3600], _drain,
                            scale=0.002, seed=3, backend="pallas",
                            chunk_s=3600, duration_s=2 * 86_400)
    written = sum(ctrl.store.get(k).nbytes() for k in ctrl.store.list())
    return reports, written


def test_every_monolithic_report_carries_the_call_totals(monolithic):
    first, _, second = monolithic
    for reports in (first, second):
        assert all(r.spans == reports[0].spans for r in reports)
        assert all(r.counts == reports[0].counts for r in reports)
        assert MONOLITHIC_TOP <= set(reports[0].spans)
        assert "nsa.tables" in reports[0].spans
        assert "nsa.device_wait" in reports[0].spans
    assert "controller.posd" in first[0].spans       # a store miss
    assert "controller.posd" not in second[0].spans  # originals stored
    assert "store.read" in second[0].spans
    assert second[0].counts["store.bytes_read"] > 0


def test_every_chunked_report_carries_the_call_totals(chunked):
    reports, _ = chunked
    assert all(r.spans == reports[0].spans for r in reports)
    assert CHUNKED_TOP <= set(reports[0].spans)
    for inner in ("nsa.tables", "chunk.pipeline", "chunk.device_wait",
                  "chunk.feed_wait", "engine.materialize", "store.write"):
        assert inner in reports[0].spans


def test_report_timings_are_the_spans(monolithic, chunked):
    first, _, second = monolithic
    for r in first + second:
        assert r.nsa_s == r.spans["nsa.leg"]
        assert r.produce_s == r.spans["replay.loop"]
    for r in chunked[0]:
        assert r.nsa_s == r.spans["chunk.pipeline"]
        assert r.produce_s == r.spans["replay.loop"]
    # preprocess_s is per dataset, the span the call's sum over datasets
    assert sum(r.preprocess_s for r in second if r.max_range == 60) == \
        pytest.approx(second[0].spans["controller.prepare"])


def test_bytes_written_are_the_stored_streams_bytes(monolithic, chunked):
    first, written, _ = monolithic
    assert first[0].counts["store.bytes_written"] == written
    reports, written = chunked
    assert reports[0].counts["store.bytes_written"] == written


def test_span_names_leave_the_window_spans_to_the_benchmark(monolithic,
                                                            chunked):
    names = set(chunked[0][0].spans)
    for reports in monolithic[::2]:
        names |= set(reports[0].spans)
    assert not names & {"sweep", "reset"}
    assert all(re.fullmatch(r"[a-z]+\.[a-z_]+", n) for n in names), names


def _chunked_calls(tmp_path, recorders, scale, chunk_s):
    Controller(str(tmp_path / f"s{scale}_{chunk_s}")).run_many(
        ["traffic"], [3600], _drain, scale=scale, seed=3, backend="pallas",
        chunk_s=chunk_s, duration_s=2 * 86_400)
    return recorders[-1].calls()


def test_spans_grow_with_chunks_not_with_records(tmp_path, recorders):
    # two days at max_range 3600 span 7200 s: 2 chunks, then 4
    base = _chunked_calls(tmp_path, recorders, 0.001, 3600)
    more_records = _chunked_calls(tmp_path, recorders, 0.002, 3600)
    more_chunks = _chunked_calls(tmp_path, recorders, 0.001, 1800)
    assert more_records == base
    assert base["chunk.device_wait"] == 2
    for name in ("chunk.device_wait", "engine.materialize",
                 "chunk.feed_wait"):
        assert more_chunks[name] == 2 * base[name]
    assert more_chunks["chunk.pipeline"] == base["chunk.pipeline"] == 1
