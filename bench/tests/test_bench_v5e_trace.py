"""The reduction on a real trace: one small sweep of ``t123_day_sweep``
(``scale=0.002``) recorded on a TPU v5e with ``bench/tests/drive.py
--trace-dir`` and stored gzipped under ``data/``."""

import gzip
import os
import re

import pytest

from benchlib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_t123_small.xplane.pb.gz")

#: readers whose kernels the monolithic sweep runs (the chunk-carry variant
#: of the metrics kernel runs only on the chunked path)
MONOLITHIC = ("stream_sample_roofline", "compaction_device_s",
              "metrics_fused_roofline")


@pytest.fixture(scope="module")
def summary():
    from jax._src.profiler import ProfileData

    with gzip.open(DATA) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return trace.reduce_profile(pd)


def test_v5e_trace_has_one_tpu_busy_inside_the_sweep(summary):
    assert summary.n_devices == 1
    assert summary.spans == {"sweep": 1}
    assert 0 < summary.busy_s < summary.window_s


def test_v5e_gaps_are_named_and_longest_first(summary):
    lengths = [s for _, s in summary.gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert all(name.startswith("sweep") for name, _ in summary.gaps)


@pytest.mark.parametrize("metric", MONOLITHIC)
def test_v5e_reader_tables_find_their_kernels(metric, summary):
    """Each reader's kernel table matches modules the chip ran, with
    device time."""
    import importlib.util

    from benchlib.spec import BENCH_DIR

    spec = importlib.util.spec_from_file_location(
        f"bench_v5e_{metric}", BENCH_DIR / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert summary.kernel_calls(mod.KERNELS) > 0
    assert summary.kernel_s(mod.KERNELS) > 0
    names = [n for n, _ in summary.top_modules(100)]
    for pattern in mod.KERNELS:
        if "carry" in pattern:
            continue
        assert any(re.search(pattern, n) for n in names), (pattern, names)
