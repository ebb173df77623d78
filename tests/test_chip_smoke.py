"""CPU tests of ``chip_smoke.py``'s checks and of the device-mode report.

The smoke itself needs a TPU; here its phases run at a tiny scale with the
Pallas kernels in interpret mode, which exercises the same comparison code
the chip run relies on.
"""

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.streamsim import Controller  # noqa: E402


@pytest.fixture(scope="module")
def sweeps(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke")
    problems, runs = chip_smoke.one_chip(str(root), scale=0.002,
                                         backend="pallas")
    return problems, {s.label: s for s in runs}


def test_tiny_smoke_passes_its_checks(sweeps):
    problems, runs = sweeps
    assert problems == []
    assert {r.mode for r in runs["device"].reports} == {"device"}
    assert {r.mode for r in runs["chunked"].reports} == {"device"}
    assert {r.mode for r in runs["numpy"].reports} == {"host"}


def test_check_run_flags_host_mode_and_bad_status(sweeps):
    _, runs = sweeps
    ref = runs["numpy"]
    assert chip_smoke.check_run(ref, "host") == []
    problems = chip_smoke.check_run(ref, "device")
    assert len(problems) == len(ref.reports)
    assert all("host" in p for p in problems)
    bad = dataclasses.replace(ref, reports=[
        dataclasses.replace(ref.reports[0], status="partial")] +
        ref.reports[1:])
    assert any("status" in p for p in chip_smoke.check_run(bad, "host"))


def test_compare_flags_differences(sweeps):
    _, runs = sweeps
    ref, dev = runs["numpy"], runs["device"]
    assert chip_smoke.compare(ref, dev) == []
    r0 = dev.reports[0]
    vol = dataclasses.replace(r0.simulated_volatility,
                              average=r0.simulated_volatility.average * 1.01)
    off = dataclasses.replace(dev, reports=[
        dataclasses.replace(r0, simulated_volatility=vol,
                            simulated_rows=r0.simulated_rows + 1)] +
        dev.reports[1:])
    problems = chip_smoke.compare(ref, off)
    assert any("simulated_volatility.average" in p for p in problems)
    assert any("simulated_rows" in p for p in problems)


def test_compare_flags_a_changed_stream(sweeps, tmp_path):
    _, runs = sweeps
    ref, dev = runs["numpy"], runs["device"]
    r0 = dev.reports[0]
    key = f"{r0.dataset}__sim{r0.max_range}"
    moved = Controller(str(tmp_path)).store
    for r in dev.reports:
        k = f"{r.dataset}__sim{r.max_range}"
        moved.put(k, dev.store.get(k))
    s = moved.get(key)
    moved.put(key, dataclasses.replace(s, t=s.t + 1.0))
    problems = chip_smoke.compare(ref, dataclasses.replace(dev, store=moved))
    assert problems == [f"device vs numpy {r0.dataset}/{r0.max_range}: "
                        "stored streams differ"]


def test_placement_records_device_runs_only(sweeps):
    import jax

    _, runs = sweeps
    dev = runs["device"]
    assert set(dev.placement) == {(r.dataset, r.max_range)
                                  for r in dev.reports}
    assert {(slot, tuple(on)) for slot, on in dev.placement.values()} == \
        {(0, (str(jax.devices()[0]),))}
    assert runs["chunked"].placement == {} == runs["numpy"].placement


def test_shard_placement_flags_misplaced_arrays(sweeps):
    _, runs = sweeps
    dev = runs["device"]
    devices = ["chip0", "chip1"]
    scs = sorted(dev.placement)
    spread = {sc: (i % 2, [devices[i % 2]]) for i, sc in enumerate(scs)}
    assert chip_smoke.shard_placement(
        dataclasses.replace(dev, placement=spread), devices) == []
    bad = dict(spread)
    bad[scs[1]] = (1, ["chip0"])
    problems = chip_smoke.shard_placement(
        dataclasses.replace(dev, placement=bad), devices)
    assert problems == [f"device {scs[1][0]}/{scs[1][1]}: arrays on "
                        "['chip0'], not chip1"]
    one = {sc: (0, ["chip0"]) for sc in scs}
    assert chip_smoke.shard_placement(
        dataclasses.replace(dev, placement=one), devices) == [
        "shards used 1 device(s) of 2"]
    assert "placement known for 0" in chip_smoke.shard_placement(
        dataclasses.replace(dev, placement={}), devices)[0]


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("chunk_s", [0, 60])
def test_domain_fallback_warns_and_reports_host_mode(tmp_path, monkeypatch,
                                                     chunk_s):
    def refuse(*args, **kw):
        raise ops.KeepRuleOverflow("bucket with count=7 and k=9 overflows")

    monkeypatch.setattr(ops, "_nsa_tables", refuse)
    ctrl = Controller(str(tmp_path))
    with pytest.warns(ops.HostFallbackWarning, match="count=7"):
        reports = ctrl.run_many(["traffic"], [60, 120],
                                chip_smoke.count_records, scale=0.002,
                                backend="pallas", chunk_s=chunk_s)
    assert [r.mode for r in reports] == ["host", "host"]
    for r in reports:
        assert r.consumer_metrics["consumed_records"] == r.simulated_rows


def test_single_stream_nsa_fallback_warns():
    from repro.kernels.stream_sample import MAX_RANGE_LIMIT
    from repro.streamsim.nsa import nsa
    from repro.streamsim.preprocess import Stream

    s = Stream("x", np.arange(100, dtype=np.float64), {"v": np.arange(100)})
    with pytest.warns(ops.HostFallbackWarning, match="nsa"):
        nsa(s, MAX_RANGE_LIMIT + 1, backend="pallas")


def test_compile_cache_dir(monkeypatch):
    import jax

    from repro import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        here = str(REPO / ".jax_cache")
        assert compile_cache.enable() == here
        assert jax.config.jax_compilation_cache_dir == here
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
