"""The benchmark's pieces on the CPU: discovery by name, the yardstick's
counts and peaks, the reference against the program's host path, the
control, and the refusal to run without a TPU."""

import json
import math
import shutil

import numpy as np
import pytest

from benchlib import check, peaks, spec, work
from benchlib.spec import ROOT


def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert set(cell.config["limits"]) == set(check.NAMES)
        assert isinstance(cell.traffic["keep_simulated"], bool)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]


def test_a_cell_added_as_files_is_found_without_editing_any(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "t123_day_burst", "config": "paper_t123_day",
        "traffic": "burst", "chips": 1, "why": "a mix added as data"})
    bench["per_layer"].append({
        "name": "sweeps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "harness",
        "moves": "sweep_rec_per_s", "workloads": ["t123_day_burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"keep_simulated": False, "warmup_sweeps": 3}))
    (tmp_path / "bench" / "metrics" / "sweeps_in_window.py").write_text(
        "def read(run):\n    return len(run.sweeps)\n")
    cell = spec.load_cell("t123_day_burst", root=tmp_path)
    assert cell.traffic["warmup_sweeps"] == 3
    assert cell.config["name"] == "paper_t123_day"
    assert [m["name"] for m in cell.per_layer] == ["sweeps_in_window"]
    read = spec.reader("sweeps_in_window", root=tmp_path)
    assert read(type("Run", (), {"sweeps": [1, 2, 3]})()) == 3
    with pytest.raises(KeyError):
        spec.load_cell("t123_day_burst")      # the real tree is untouched


@pytest.mark.parametrize("name", ["sogouq", "traffic", "userbehavior"])
def test_seeds_reorder_the_same_arrivals(name):
    """Every seed gets the same per-second counts in another order: the
    record count and each 1 440 s window's count are the seed's own only in
    their order, while the arrivals themselves differ."""
    from benchlib import generators as g

    a = g.arrivals(name, 0.05, 0, 7)
    b = g.arrivals(name, 0.05, 0, 2 ** 31 + 7)
    assert len(a) == len(b)
    assert not np.array_equal(a, b)
    per = np.arange(0, g.DAY + 1, g.WINDOW_S)
    assert np.array_equal(np.histogram(a, per)[0], np.histogram(b, per)[0])
    assert not np.array_equal(np.histogram(a, g.DAY // g.BLOCK_S)[0],
                              np.histogram(b, g.DAY // g.BLOCK_S)[0])


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_stream_sample_work_counts_valid_records_only():
    # two rows of 1000 and 10 valid records, tables of 600 and 3600
    b, ops = work.stream_sample([1000, 10], [600, 3600])
    assert b == 1010 * 9 + 4200 * 12
    assert ops == 1010 * 8
    # padding a row to the tile changes nothing: only valid records count
    assert work.stream_sample([1000, 10], [600, 3600]) == (b, ops)
    assert work.stream_sample([1024, 1024], [600, 3600])[0] > b


def test_stream_metrics_work_counts_stamps_and_own_widths():
    b, ops = work.stream_metrics([100, 7], [600, 86_400])
    assert b == 4 * 107 + 4 * 87_000 + 8 * 2
    assert ops == 107 + 2 * 87_000


def test_roofline_takes_the_binding_bound():
    t = peaks.least_time_s(819e9, 1.0, "TPU v5 lite")
    assert math.isclose(t, 1.0)
    t = peaks.least_time_s(1.0, 197e12, "TPU v5 lite")
    assert math.isclose(t, 1.0)


def test_program_host_path_equals_the_reference(drive):
    """The program's backend="numpy" output, through the whole harness,
    matches the benchmark's own reference to rounding."""
    res = drive("t123_day_sweep", "none", "--backend", "numpy")
    c = res["checks"]
    assert res["correct"] is True
    for k in ("streams_differ", "deliveries_differ", "rows_differ"):
        assert c[k]["value"] == 0
    assert c["stat_rel_err"]["value"] < 1e-9
    assert c["corr_err"]["value"] < 1e-9


def test_control_comes_out_incorrect(child):
    rc, out, err = child("bench/control.py", "--workload", "t123_day_sweep",
                         "--seeds", "3", "--scale", "0.002")
    assert rc == 0, err[-3000:]
    row = json.loads(out.strip().splitlines()[0])
    assert row["correct"] is False


def test_run_without_a_tpu_fails_and_prints_no_result(child):
    rc, out, err = child("bench/run.py", "--workload", "t123_day_sweep",
                         "--seed", "1", "--seconds", "1", "--trace", "0",
                         timeout=300)
    assert rc != 0
    assert "needs a TPU" in err
    assert not any(line.startswith("{") for line in out.splitlines())
