"""Dry-run machinery tests: HLO cost analyzer validation + a reduced-mesh
lower/compile in a subprocess (the 512-device flag must not leak into this
process)."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo

REPO = pathlib.Path(__file__).parent.parent


class TestHloAnalyzer:
    def test_loop_free_matches_xla(self):
        def f(x, w1, w2):
            return ((x @ w1) @ w2).sum()

        args = [jax.ShapeDtypeStruct(s, jnp.float32)
                for s in [(64, 128), (128, 256), (256, 512)]]
        c = jax.jit(f).lower(*args).compile()
        xla = c.cost_analysis()
        mine = analyze_hlo(c.as_text())
        exact = 2 * 64 * 128 * 256 + 2 * 64 * 256 * 512
        assert abs(mine["flops"] - exact) / exact < 0.01
        # bytes: ours models TPU dot-epilogue fusion (single-use dot outputs
        # stay on-chip), so it must be <= XLA's count and within ~2x
        assert mine["bytes"] <= xla["bytes accessed"] * 1.05
        assert mine["bytes"] >= xla["bytes accessed"] * 0.3

    def test_scan_trip_count_applied(self):
        def layer(x, w):
            return jax.nn.gelu(x @ w), None

        def g(x, ws):
            y, _ = jax.lax.scan(layer, x, ws)
            return y.sum()

        args = [jax.ShapeDtypeStruct((64, 128), jnp.float32),
                jax.ShapeDtypeStruct((32, 128, 128), jnp.float32)]
        c = jax.jit(g).lower(*args).compile()
        mine = analyze_hlo(c.as_text())
        exact = 32 * 2 * 64 * 128 * 128
        assert abs(mine["flops"] - exact) / exact < 0.01, \
            "while bodies must be multiplied by trip count"
        # XLA's own count misses the loop: stays far below exact
        assert c.cost_analysis()["flops"] < exact / 4

    def test_scan_bytes_not_inflated_by_stacked_params(self):
        # a scan reading one (128,128) slice per step must not count the
        # whole (32,128,128) stack per iteration
        def layer(x, w):
            return jax.nn.gelu(x @ w), None

        def g(x, ws):
            y, _ = jax.lax.scan(layer, x, ws)
            return y.sum()

        args = [jax.ShapeDtypeStruct((64, 128), jnp.float32),
                jax.ShapeDtypeStruct((32, 128, 128), jnp.float32)]
        c = jax.jit(g).lower(*args).compile()
        mine = analyze_hlo(c.as_text())
        # slice traffic: 32 iters * [x(64,128)*3-ish + w(128,128)*2] * 4B
        upper = 32 * (6 * 64 * 128 + 3 * 128 * 128) * 4
        assert mine["bytes"] < upper

    def test_grad_flops_ratio(self):
        # grad of matmul chain should cost ~3x forward
        def f(w, x):
            return ((x @ w) ** 2).sum()

        wspec = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        xspec = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        fwd = analyze_hlo(jax.jit(f).lower(wspec, xspec).compile().as_text())
        bwd = analyze_hlo(jax.jit(jax.grad(f)).lower(
            wspec, xspec).compile().as_text())
        assert 1.5 <= bwd["flops"] / fwd["flops"] <= 3.5


SMOKE_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, json
import repro.launch.dryrun as D
import repro.launch.mesh as M
# shrink the production mesh for a CPU-sized smoke of the same code path
M.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
    (2, 2, 4) if multi_pod else (4, 4),
    ("pod", "data", "model") if multi_pod else ("data", "model"),
    M.auto_axes(3 if multi_pod else 2))
D.make_production_mesh = M.make_production_mesh
import repro.configs as C
# reduced shapes so a smoke config lowers in seconds
C.SHAPES = {
    "train_4k": C.ShapeSpec("train_4k", 64, 8, "train"),
    "decode_32k": C.ShapeSpec("decode_32k", 64, 8, "decode"),
}
D.SHAPES = C.SHAPES
import repro.configs.llama3_8b as L
cfgs = {"llama3-8b": L.smoke().replace(loss_chunk=16)}
D.get_config = lambda a: cfgs[a]
for shape in ("train_4k", "decode_32k"):
    for mesh in ("single", "multi"):
        r = D.run_cell("llama3-8b", shape, mesh, verbose=False)
        assert r["ok"], r.get("error")
        assert r["hlo_flops"] > 0
        assert r["roofline"]["dominant"] in ("compute_s", "memory_s",
                                             "collective_s")
print("DRYRUN_SMOKE_OK")
"""


class TestDryRunSmoke:
    def test_reduced_mesh_cells_compile(self):
        r = subprocess.run(
            [sys.executable, "-c", SMOKE_DRYRUN], capture_output=True,
            text=True, timeout=900,
            env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO)
        assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
        assert "DRYRUN_SMOKE_OK" in r.stdout
