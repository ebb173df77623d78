import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must
succeed on the single-pod (16, 16) mesh AND the 2-pod (2, 16, 16) mesh for
every supported cell. The compiled artifact also supplies the roofline
inputs: ``cost_analysis()`` (HLO FLOPs / bytes), ``memory_analysis()``
(per-device footprint), and the post-SPMD HLO text (collective schedule).

Usage::

    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, cell_supported, get_config, input_specs
from repro.launch.mesh import (
    HBM_PER_CHIP,
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    make_production_mesh,
)
from repro.launch.hlo_analysis import analyze_hlo
from repro.models import transformer
from repro.training.optimizer import AdamW
from repro.training.steps import jit_prefill_step, jit_serve_step, jit_train_step

# ---------------------------------------------------------------- HLO parse
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2, "c64": 8}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo: str):
    """Per-device bytes moved by collectives, from post-SPMD HLO text.

    Ring-algorithm accounting per participating device (g = group size):
      all-gather        out * (g-1)/g
      all-reduce        2 * out * (g-1)/g
      reduce-scatter    out * (g-1)          (input = out*g)
      all-to-all        out * (g-1)/g
      collective-permute out
    """
    per_op = {}
    total = 0.0
    for m in _COLL_RE.finditer(hlo):
        type_str, kind = m.group(1), m.group(2)
        if "-done(" in m.group(0):
            continue  # avoid double counting start/done pairs
        out_b = _shape_bytes(type_str)
        line_end = hlo.find("\n", m.end())
        line = hlo[m.start():line_end]
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            if gb:
                g = len(gb.group(1).split(","))
        if kind == "all-gather":
            b = out_b * (g - 1) / max(g, 1)
        elif kind == "all-reduce":
            b = 2 * out_b * (g - 1) / max(g, 1)
        elif kind == "reduce-scatter":
            b = out_b * (g - 1)
        elif kind == "all-to-all":
            b = out_b * (g - 1) / max(g, 1)
        else:  # collective-permute
            b = out_b
        rec = per_op.setdefault(kind, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += b
        total += b
    return per_op, total


# -------------------------------------------------------------- model flops
def model_flops(cfg, shape_name: str) -> float:
    """6·N_active·D for train; 2·N_active·B (+cache attention) for decode."""
    spec = SHAPES[shape_name]
    n_active = active_params(cfg)
    b, s = spec.global_batch, spec.seq_len
    if spec.kind == "train":
        return 6.0 * n_active * b * s
    attn_per_tok = 0.0
    for kind in cfg.blocks():
        mixer = kind.split(":")[0]
        if mixer in ("attn", "local"):
            ctx = min(s, cfg.window) if (mixer == "local" and cfg.window) else s
            if cfg.mla:
                attn_per_tok += 2 * cfg.n_heads * ctx * (
                    2 * cfg.kv_lora_rank + cfg.qk_rope_dim)
            else:
                attn_per_tok += 4 * cfg.n_heads * cfg.head_dim_ * ctx
    if spec.kind == "prefill":
        # causal triangle: average context s/2
        return 2.0 * n_active * b * s + b * attn_per_tok * s / 2
    return b * (2.0 * n_active + attn_per_tok)


def active_params(cfg) -> float:
    n = cfg.n_params()
    if cfg.n_experts > 0:
        per_expert = 3 * cfg.d_model * cfg.d_ff_expert
        n_moe_layers = sum(1 for k in cfg.blocks() if k.endswith(":moe"))
        inactive = n_moe_layers * (cfg.n_experts - cfg.top_k) * per_expert
        n -= inactive
    return float(n)


# ------------------------------------------------------------------ lowering
def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               overrides: dict | None = None):
    cfg = get_config(arch)
    shard_seq = False
    if overrides:
        overrides = dict(overrides)
        shard_seq = overrides.pop("shard_seq", False)
        if overrides:
            cfg = cfg.replace(**overrides)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    specs = input_specs(cfg, shape_name)

    if spec.kind == "train":
        opt = AdamW()
        step = jit_train_step(cfg, opt, mesh, policy="fsdp_tp", donate=True,
                              shard_seq=shard_seq)
        pshape = transformer.param_specs(cfg)
        oshape = jax.eval_shape(lambda: {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.float32), pshape),
            "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.float32), pshape)})
        args = (pshape, oshape, specs["batch"])
    elif spec.kind == "prefill":
        step = jit_prefill_step(cfg, mesh)
        pshape = transformer.param_specs(cfg)
        args = (pshape, specs["inputs"], specs["lengths"])
    else:  # decode
        step = jit_serve_step(cfg, mesh, batch=spec.global_batch,
                              max_len=spec.seq_len, donate=True)
        pshape = transformer.param_specs(cfg)
        args = (pshape, specs["cache"], specs["tokens"])

    t0 = time.perf_counter()
    lowered = step.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    return cfg, mesh, lowered, compiled, t_lower, t_compile


def analyze(arch: str, shape_name: str, mesh_kind: str, cfg, mesh, lowered,
            compiled, t_lower, t_compile) -> dict:
    n_dev = int(np.prod(list(mesh.shape.values())))
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-aware HLO analysis (cost_analysis undercounts while bodies);
    # the compiled module is the per-device SPMD program, so flops/bytes
    # are already per-device.
    hc = analyze_hlo(hlo)
    per_op, coll_bytes = hc["collectives"], hc["collective_bytes"]

    flops = float(hc["flops"])
    bytes_accessed = float(hc["bytes"])
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_accessed / HBM_BW
    t_coll = coll_bytes / ICI_BW  # per-device bytes
    mf = model_flops(cfg, shape_name)

    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf_per_dev = model_flops(cfg, shape_name) / n_dev
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_devices": n_dev, "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_flops": flops, "hlo_bytes": bytes_accessed,
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "collective_bytes_per_device": coll_bytes,
        "collectives": per_op,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "hbm_fraction": per_dev_bytes / HBM_PER_CHIP,
        },
        "roofline": {
            **{k: v for k, v in terms.items()},
            "dominant": dominant,
            "step_time_lower_bound_s": max(terms.values()),
            "model_flops": mf,
            "model_flops_per_device": mf_per_dev,
            "useful_flops_ratio": mf_per_dev / flops if flops else 0.0,
            "roofline_fraction": (mf_per_dev / PEAK_FLOPS_BF16)
                                 / max(max(terms.values()), 1e-12),
        },
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None, verbose: bool = True) -> dict:
    try:
        out = analyze(arch, shape_name, mesh_kind,
                      *lower_cell(arch, shape_name, mesh_kind, overrides))
    except Exception as e:  # noqa: BLE001 — recorded, the driver decides
        out = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    if verbose:
        if out["ok"]:
            r = out["roofline"]
            print(f"[OK] {arch} × {shape_name} × {mesh_kind}: "
                  f"compile={out['compile_s']}s "
                  f"flops={out['hlo_flops']:.3e} "
                  f"mem/dev={out['memory']['per_device_bytes']/2**30:.2f}GiB "
                  f"dominant={r['dominant']} "
                  f"bound={r['step_time_lower_bound_s']:.4f}s")
        else:
            print(f"[FAIL] {arch} × {shape_name} × {mesh_kind}: "
                  f"{out['error']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf loop)")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    overrides = json.loads(args.override) if args.override else None
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            cfg = get_config(a)
            for s in SHAPES:
                if cell_supported(cfg, s):
                    cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for a, s in cells:
        for m in meshes:
            if not cell_supported(get_config(a), s):
                print(f"[SKIP] {a} × {s}: full-attention arch, long-context "
                      f"cell unsupported (DESIGN.md §Arch-applicability)")
                res = {"arch": a, "shape": s, "mesh": m, "ok": True,
                       "skipped": True,
                       "reason": "full attention: 500k decode needs "
                                 "sub-quadratic mixer"}
                fn = outdir / f"{args.tag}__{a}__{s}__{m}.json"
                with open(fn, "w") as f:
                    json.dump(res, f, indent=2)
                continue
            res = run_cell(a, s, m, overrides)
            fn = outdir / f"{args.tag}__{a}__{s}__{m}.json"
            with open(fn, "w") as f:
                json.dump(res, f, indent=2)
            n_fail += 0 if res["ok"] else 1
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
