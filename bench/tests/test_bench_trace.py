"""The reduction from a profiler trace to busy time, module time and named
idle gaps, on a synthetic trace with known answers."""

import pytest

from benchlib import trace

# times in picoseconds: host spans sweep [0, 10 us), reset [10, 12 us);
# device ops [1, 2), [1.5, 3) (overlapping), [5, 6) and [11, 13) us, the
# last clipped by the window's end at 12 us
SYNTHETIC = '''
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sweep" } }
  event_metadata { key: 2 value { id: 2 name: "reset" } }
  event_metadata { key: 3 value { id: 3 name: "np.asarray" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_stream_sample_pallas(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__scatter_kept(9)" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.1" } }
  event_metadata { key: 4 value { id: 4 name: "scatter.2" } }
}
'''


@pytest.fixture(scope="module")
def summary():
    from jax._src.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_window_is_spanned_by_the_harness_spans(summary):
    assert summary.window_s == pytest.approx(12e-6)
    assert summary.spans == {"sweep": 1, "reset": 1}


def test_busy_is_the_union_clipped_to_the_window(summary):
    # [1, 3) + [5, 6) + [11, 12) us
    assert summary.busy_s == pytest.approx(4e-6)
    assert summary.n_devices == 1


def test_module_time_sums_its_operations(summary):
    assert summary.kernel_s([r"stream_sample_pallas"]) == \
        pytest.approx((1 + 1.5 + 1) * 1e-6)
    assert summary.kernel_s([r"_scatter_kept"]) == pytest.approx(1e-6)
    assert summary.kernel_calls([r"stream_sample_pallas"]) == 2
    assert summary.kernel_calls([r"gather_kept"]) == 0


def test_gaps_are_named_by_what_the_host_did(summary):
    # idle [6, 11) us (middle in the sweep), [3, 5) us (middle under
    # np.asarray), [0, 1) us, longest first
    assert summary.gaps == [("sweep", pytest.approx(5e-6)),
                            ("sweep/np.asarray", pytest.approx(2e-6)),
                            ("sweep", pytest.approx(1e-6))]


def _kernel_tables():
    import importlib.util

    from benchlib.spec import BENCH_DIR

    tables = {}
    for path in sorted((BENCH_DIR / "metrics").glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"bench_table_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, "KERNELS"):
            tables[path.stem] = mod.KERNELS
    return tables


@pytest.fixture(scope="module")
def executed_modules(child):
    import json

    rc, out, err = child("bench/tests/module_names.py")
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("metric", sorted(_kernel_tables()))
def test_reader_tables_name_modules_the_program_runs(metric,
                                                     executed_modules):
    """Every name pattern a trace reader matches is the module of a
    kernel a real sweep runs: a renamed kernel fails here, not silently
    in a traced run."""
    import re

    for pattern in _kernel_tables()[metric]:
        assert any(re.search(pattern, m) for m in executed_modules), \
            (pattern, executed_modules)
