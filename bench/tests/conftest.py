import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    """Run a benchmark script in a child process from the repository root,
    on the CPU with no TPU and with a compile cache of its own; returns
    ``(exit code, stdout, stderr)``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # one host thread each, so the children do not crowd the other test
    # workers off the cores (they run no faster with more)
    env["XLA_FLAGS"] = ("--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1")
    env["OMP_NUM_THREADS"] = "1"

    def run(*args, timeout=600):
        p = subprocess.run([sys.executable] + list(args), cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=timeout)
        return p.returncode, p.stdout, p.stderr
    return run


@pytest.fixture(scope="module")
def drive(child):
    """One planted-fault run of a cell (``bench/tests/drive.py``) at a
    tiny scale; returns its result object."""
    def run(workload, fault="none", *extra):
        rc, out, err = child("bench/tests/drive.py", "--workload", workload,
                             "--fault", fault, *extra)
        assert rc == 0, err[-4000:]
        return json.loads(out.strip().splitlines()[-1])
    return run
