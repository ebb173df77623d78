"""Shape-keyed tile autotuner for the Pallas kernel layer.

Every ``pl.pallas_call`` in :mod:`repro.kernels.stream_sample`,
:mod:`repro.kernels.metrics_fused`, :mod:`repro.kernels.trend_scan` and
:mod:`repro.kernels.compact` is parameterized on a :class:`TileConfig`
``(record_tile, bucket_block, grid_split)`` instead of hard module
constants, and this module decides which config a dispatch gets:

1. **Heuristic chooser** (``autotune="off"``, the default) — a pure
   function of the :class:`TuneKey` (problem shape pow2-snapped + device
   kind). On TPU and on the CPU ``interpret`` path it returns exactly the
   constants the kernels shipped with (``record_tile = 1024``,
   ``bucket_block = 512``, ``grid_split = 1``), so the default path is
   bit-for-bit identical to the pre-tuner kernels. GPU device kinds get a
   pow2-snapped choice (the A100-style ``_choose_pow2`` tiling-chooser
   pattern), clamped to the VMEM footprint budget.
2. **Measured sweep** (``autotune="cached"|"force"``) — a small candidate
   lattice is timed on the real device (min-of-reps), each candidate
   **oracle-gated** against the pure-jnp references in
   :mod:`repro.kernels.ref` before it is eligible (a config that is fast
   but wrong is discarded), and the winner is persisted in a JSON cache
   keyed by ``device kind + TuneKey``. ``"cached"`` reuses persisted
   winners; ``"force"`` re-measures and overwrites them.

The persisted cache lives *under the store* (``StreamStore``-adjacent):
one marker ``_markers/_tune/<device-kind>.json`` per device kind, written
through :meth:`repro.streamsim.store.StreamStore.put_marker` — the same
tempfile + ``os.replace`` atomic-write primitive the sweep service trusts
— so concurrent writers always leave a valid JSON file (in-process
writers additionally merge through a module lock, cross-process writers
are last-merge-wins). A corrupt or partially-written cache file is
*never* an error: loading falls back to the heuristic defaults.

Wiring: the ops wrappers consult the **ambient** tuner
(:func:`config_for` → :func:`current`) at every dispatch, and the layers
above (``nsa``/``metrics`` → ``engine``/``ChunkedSweepRunner`` →
``Controller.run/run_many``) accept an ``autotune=`` knob that installs a
shared tuner via :func:`tuner_context` around their device legs — so
every existing dispatch shape (monolithic, chunked, sharded, service)
inherits tuned tiles without per-call plumbing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

LANE = 128
#: TPU sublane granularity for int32/float32 blocks: record tiles are
#: (sublane, LANE) with sublane a multiple of 8 (see the Pallas tiling
#: constraints), i.e. ``record_tile % 1024 == 0``.
MIN_RECORD_TILE = 8 * LANE

DEFAULT_RECORD_TILE = MIN_RECORD_TILE       # 1024 — the pre-tuner TILE
DEFAULT_BUCKET_BLOCK = 4 * LANE             # 512 — BUCKET_BLOCK/PAIR_TILE

#: Footprint budget for the largest tile-shaped intermediate a config can
#: make the kernels materialize (the metrics engine's one-hot
#: ``(record_tile, bucket_block)`` i32 tile): half of a TPU core's
#: ~16 MiB VMEM, leaving room for the resident histogram/Gram blocks.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Kernel families a TileConfig can parameterize (TuneKey.kernel values).
KERNELS = ("stream_sample", "metrics_fused", "trend_scan", "pair_stats",
           "compact")

AUTOTUNE_MODES = ("off", "cached", "force")

#: Store marker namespace holding the per-device-kind JSON caches.
TUNE_NAMESPACE = "_tune"

#: Measured-sweep candidate axes (filtered per key by the VMEM budget and
#: the problem size — a tile wider than the padded problem never wins).
LATTICE_RECORD_TILES = (1024, 2048)
LATTICE_BUCKET_BLOCKS = (256, 512, 1024)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One kernel tiling choice: ``(record_tile, bucket_block, grid_split)``.

    record_tile  : records (or time steps) per grid step — the (sublane,
                   LANE) block height times LANE; must be a positive
                   multiple of ``MIN_RECORD_TILE`` (= 8·128 = 1024).
    bucket_block : bucket (or pair-tile) columns processed per inner step
                   — the metrics engine's one-hot width and the
                   pair-stats kernel's time tile; a positive LANE
                   multiple.
    grid_split   : number of row groups the *batch* axis of the NSA sweep
                   dispatch is split into (``1`` = today's single
                   launch); a VMEM relief valve for huge (S × tables)
                   problems.

    Frozen + hashable so it can ride ``jax.jit`` static arguments — each
    distinct config compiles its own kernel specialization.
    """

    record_tile: int = DEFAULT_RECORD_TILE
    bucket_block: int = DEFAULT_BUCKET_BLOCK
    grid_split: int = 1

    def __post_init__(self):
        if self.record_tile <= 0 or self.record_tile % MIN_RECORD_TILE:
            raise ValueError(
                f"record_tile {self.record_tile} must be a positive "
                f"multiple of {MIN_RECORD_TILE}")
        if self.bucket_block <= 0 or self.bucket_block % LANE:
            raise ValueError(
                f"bucket_block {self.bucket_block} must be a positive "
                f"multiple of {LANE}")
        if self.grid_split < 1:
            raise ValueError(f"grid_split {self.grid_split} must be >= 1")

    @property
    def sublane(self) -> int:
        """Block height of the (sublane, LANE) record tile."""
        return self.record_tile // LANE

    def vmem_bytes(self, itemsize: int = 4) -> int:
        """Footprint of the largest tile-shaped intermediate (the metrics
        one-hot ``(record_tile, bucket_block)`` tile)."""
        return self.record_tile * self.bucket_block * itemsize

    def as_dict(self) -> Dict[str, int]:
        return {"record_tile": self.record_tile,
                "bucket_block": self.bucket_block,
                "grid_split": self.grid_split}

    @classmethod
    def from_dict(cls, d: Dict) -> "TileConfig":
        return cls(record_tile=int(d["record_tile"]),
                   bucket_block=int(d["bucket_block"]),
                   grid_split=int(d.get("grid_split", 1)))


DEFAULT_CONFIG = TileConfig()


def _pow2_snap(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """Cache key for one tuning decision.

    Shapes are pow2-snapped so nearby problems share a cache line:
    ``s``/``n``/``r`` are the snapped stream count, record/time-axis
    length, and bucket-axis width (``r = 0`` for kernels without a bucket
    axis). ``dtype`` is the record element type name. The device kind is
    NOT part of the key — the cache file itself is per device kind.
    """

    kernel: str
    s: int
    n: int
    r: int = 0
    dtype: str = "int32"

    @classmethod
    def from_shape(cls, kernel: str, *, s: int, n: int, r: int = 0,
                   dtype: str = "int32") -> "TuneKey":
        if kernel not in KERNELS:
            raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")
        return cls(kernel=kernel, s=_pow2_snap(max(s, 1)),
                   n=_pow2_snap(max(n, 1)),
                   r=_pow2_snap(r) if r > 0 else 0, dtype=str(dtype))

    def encode(self) -> str:
        return f"{self.kernel}/s{self.s}/n{self.n}/r{self.r}/{self.dtype}"

    @classmethod
    def decode(cls, text: str) -> "TuneKey":
        kernel, s, n, r, dtype = text.split("/")
        return cls(kernel=kernel, s=int(s[1:]), n=int(n[1:]), r=int(r[1:]),
                   dtype=dtype)


def _slug(text: str) -> str:
    out = "".join(c if c.isalnum() else "-" for c in text.lower())
    while "--" in out:
        out = out.replace("--", "-")
    return out.strip("-") or "unknown"


def device_kind() -> str:
    """Cache-file identity of the accelerator the kernels dispatch to.

    ``cpu-interpret`` off-accelerator (the kernels run interpreted there,
    so timings are about interpreter overhead, not silicon — still a
    valid, self-consistent tuning target for CI), else
    ``tpu-<kind>``/``gpu-<kind>`` from the first device's
    ``device_kind``.
    """
    backend = jax.default_backend()
    if backend in ("tpu", "gpu", "cuda", "rocm"):
        family = "gpu" if backend != "tpu" else "tpu"
        try:
            kind = jax.devices()[0].device_kind
        except Exception:  # pragma: no cover - no devices at all
            kind = backend
        return _slug(f"{family}-{kind}")
    return "cpu-interpret"


def heuristic_config(key: TuneKey, kind: Optional[str] = None) -> TileConfig:
    """Pure shape-keyed chooser — the ``autotune="off"`` path.

    On TPU and the CPU interpret path this returns exactly the constants
    the kernels shipped with (``1024/512/1``), making the default
    dispatch bit-for-bit identical to the pre-tuner kernels. GPU kinds
    get a pow2-snapped choice: a fatter record tile for long record axes
    (fewer, larger programs) and a bucket block snapped to the bucket
    axis width. Every returned config satisfies the lane/sublane
    alignment invariants and the :data:`VMEM_BUDGET_BYTES` footprint
    bound (clamped bucket-block-first — the cheaper axis to shrink).
    """
    kind = device_kind() if kind is None else kind
    rt, bb = DEFAULT_RECORD_TILE, DEFAULT_BUCKET_BLOCK
    if kind.startswith("gpu"):
        rt = min(max(_pow2_snap(key.n) // 4, MIN_RECORD_TILE), 4096)
        if key.r > 0:
            bb = min(max(_pow2_snap(key.r), LANE), 8 * LANE)
    while rt * bb * 4 > VMEM_BUDGET_BYTES and bb > LANE:
        bb //= 2
    while rt * bb * 4 > VMEM_BUDGET_BYTES and rt > MIN_RECORD_TILE:
        rt //= 2
    return TileConfig(record_tile=rt, bucket_block=bb, grid_split=1)


def candidate_lattice(key: TuneKey,
                      kind: Optional[str] = None) -> List[TileConfig]:
    """Measured-sweep candidates for one key: the heuristic default plus
    the :data:`LATTICE_RECORD_TILES` × :data:`LATTICE_BUCKET_BLOCKS`
    grid, filtered by the VMEM budget and pruned to tiles no wider than
    the pow2-padded problem (a 2048-record tile cannot beat a 1024 tile
    on a 300-record stream — it only pads more)."""
    cands = [heuristic_config(key, kind)]
    rt_cap = max(_pow2_snap(key.n), MIN_RECORD_TILE)
    bb_cap = max(_pow2_snap(key.r), 2 * LANE) if key.r > 0 else LANE * 8
    for rt in LATTICE_RECORD_TILES:
        if rt > rt_cap:
            continue
        for bb in LATTICE_BUCKET_BLOCKS:
            if bb > bb_cap:
                continue
            cfg = TileConfig(record_tile=rt, bucket_block=bb)
            if cfg.vmem_bytes() <= VMEM_BUDGET_BYTES and cfg not in cands:
                cands.append(cfg)
    return cands


# --------------------------------------------------------------- sweep specs
def _interpret() -> bool:
    return jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")


def _spec_rng(key: TuneKey) -> np.random.Generator:
    return np.random.default_rng(zlib.crc32(key.encode().encode()))


def _spec_shapes(key: TuneKey) -> Tuple[int, int, int]:
    """Problem sizes the sweep actually measures: the decoded key shape,
    capped so a force-sweep on an enormous key stays bounded (keys only
    differ below the caps; above them the winner generalizes)."""
    return (min(key.s, 16), min(key.n, 1 << 17),
            min(key.r, 1 << 15) if key.r > 0 else 0)


def _pad_rows(x: np.ndarray, mult: int, value) -> np.ndarray:
    pad = (-x.shape[1]) % mult
    if pad:
        fill = np.full((x.shape[0], pad), value, x.dtype)
        x = np.concatenate([x, fill], axis=1)
    return x


def _run_stream_sample(key: TuneKey, cfg: TileConfig):
    import jax.numpy as jnp

    from repro.kernels.ops import _nsa_tables, stream_sample_launch

    s, n, r = _spec_shapes(key)
    r = max(r, 2)
    rng = _spec_rng(key)
    rows = [np.sort(rng.uniform(0.0, 3600.0, n)) for _ in range(s)]
    t_b = np.empty((s, n), np.float32)
    tables = [np.empty((s, r), np.int32) for _ in range(3)]
    scal = np.empty((s, 3), np.float32)
    for i, t64 in enumerate(rows):
        t32, starts, counts, ktab, scalars = _nsa_tables(t64, r, 3.0)
        t_b[i] = t32
        tables[0][i], tables[1][i], tables[2][i] = starts, counts, ktab
        scal[i] = scalars
    tp = _pad_rows(t_b, cfg.record_tile, t_b[:, -1:].max())
    args = tuple(map(jnp.asarray, (tp, *tables, scal)))

    def run():
        ss, keep = stream_sample_launch(*args, r, cfg)
        return ss[:, :n], keep[:, :n]

    def reference():
        from repro.kernels import ref
        out = ref.stream_sample_ref(jnp.asarray(t_b), *args[1:], r)
        return out

    return run, reference, (True, True)


def _run_metrics(key: TuneKey, cfg: TileConfig):
    import jax.numpy as jnp

    from repro.kernels.metrics_fused import stream_metrics_pallas

    s, n, r = _spec_shapes(key)
    r = max(r, 2)
    rng = _spec_rng(key)
    ss = np.sort(rng.integers(0, r, (s, n)), axis=1).astype(np.int32)
    buckets = int(-(-r // cfg.bucket_block) * cfg.bucket_block)
    ssb = jnp.asarray(_pad_rows(ss, cfg.record_tile, buckets))

    def run():
        hist, mom = stream_metrics_pallas(ssb, buckets,
                                          interpret=_interpret(), config=cfg)
        return hist[:, :r], mom

    def reference():
        from repro.kernels import ref
        hist, mom = ref.stream_metrics_ref(jnp.asarray(ss), r)
        return hist, mom

    return run, reference, (True, False)


def _run_trend_scan(key: TuneKey, cfg: TileConfig):
    import jax.numpy as jnp

    from repro.kernels.trend_scan import trend_scan_pallas

    s, n, _ = _spec_shapes(key)
    rng = _spec_rng(key)
    q = rng.integers(0, 7, (s, n)).astype(np.int32)
    qp = jnp.asarray(_pad_rows(q, cfg.record_tile, 0))

    def run():
        return (trend_scan_pallas(qp, interpret=_interpret(),
                                  config=cfg)[:, :n],)

    def reference():
        from repro.kernels import ref
        return (ref.trend_scan_ref(jnp.asarray(q)),)

    return run, reference, (True,)


def _run_pair_stats(key: TuneKey, cfg: TileConfig):
    import jax.numpy as jnp

    from repro.kernels.trend_scan import pair_stats_pallas

    s, n, _ = _spec_shapes(key)
    rng = _spec_rng(key)
    x = rng.standard_normal((s, n)).astype(np.float32)
    xp = jnp.asarray(_pad_rows(x, cfg.bucket_block, 0.0))

    def run():
        return pair_stats_pallas(xp, interpret=_interpret(), config=cfg)

    def reference():
        from repro.kernels import ref
        return ref.pair_stats_ref(jnp.asarray(x))

    return run, reference, (False, False)


def _run_compact(key: TuneKey, cfg: TileConfig):
    import jax.numpy as jnp

    from repro.kernels.compact import compact_positions_batched_pallas

    s, n, _ = _spec_shapes(key)
    rng = _spec_rng(key)
    mask = (rng.random((s, n)) < 0.3).astype(np.int32)
    mp = jnp.asarray(_pad_rows(mask, cfg.record_tile, 0))

    def run():
        pos, totals = compact_positions_batched_pallas(
            mp, interpret=_interpret(), config=cfg)
        return pos[:, :n], totals

    def reference():
        from repro.kernels import ref
        m = jnp.asarray(mask)
        incl = jnp.cumsum(m, axis=1)
        return (incl - m).astype(jnp.int32), incl[:, -1:].astype(jnp.int32)

    return run, reference, (True, True)


#: kernel name -> spec builder returning (run(cfg) closure, reference()
#: closure, per-output exactness flags). The run closure executes the real
#: Pallas wrapper with an explicit config (never the ambient tuner — no
#: recursion), the reference closure the pure-jnp oracle.
_SPECS = {
    "stream_sample": _run_stream_sample,
    "metrics_fused": _run_metrics,
    "trend_scan": _run_trend_scan,
    "pair_stats": _run_pair_stats,
    "compact": _run_compact,
}


def _outputs_match(got, want, exact_flags) -> bool:
    for g, w, exact in zip(got, want, exact_flags):
        g, w = np.asarray(g), np.asarray(w)
        if exact:
            if not np.array_equal(g, w):
                return False
        elif not np.allclose(g, w, rtol=1e-3, atol=1e-3):
            return False
    return True


# ------------------------------------------------------------------- tuner
_PERSIST_LOCK = threading.Lock()


class KernelTuner:
    """Chooses a :class:`TileConfig` per dispatch shape.

    mode  : ``"off"`` — heuristic only (zero I/O, the default);
            ``"cached"`` — in-memory → persisted cache → measured sweep;
            ``"force"`` — measured sweep, overwriting any persisted
            winner (memoized in-process so a force run sweeps each key
            once, not once per dispatch).
    store : optional :class:`repro.streamsim.store.StreamStore` the JSON
            cache persists under (``None`` = in-memory only).
    kind  : device-kind override (tests tune for a fake device; real use
            leaves the default :func:`device_kind`).
    reps  : timed repetitions per candidate; the score is the min.
    """

    def __init__(self, mode: str = "off", store=None, *,
                 kind: Optional[str] = None, reps: int = 3):
        if mode not in AUTOTUNE_MODES:
            raise ValueError(
                f"autotune mode {mode!r}; one of {AUTOTUNE_MODES}")
        self.mode = mode
        self.store = store
        self.kind = device_kind() if kind is None else kind
        self.reps = max(int(reps), 1)
        self._timer = time.perf_counter
        self._mem: Dict[TuneKey, TileConfig] = {}
        self._lock = threading.Lock()

    # -- public -----------------------------------------------------------
    def config_for(self, kernel: str, *, s: int, n: int, r: int = 0,
                   dtype: str = "int32") -> TileConfig:
        """The config a dispatch of this shape should use (may sweep)."""
        key = TuneKey.from_shape(kernel, s=s, n=n, r=r, dtype=dtype)
        if self.mode == "off":
            return heuristic_config(key, self.kind)
        with self._lock:
            hit = self._mem.get(key)
        if hit is not None:
            return hit
        if self.mode == "cached":
            disk = self._load_cache().get(key)
            if disk is not None:
                with self._lock:
                    self._mem[key] = disk
                return disk
        cfg = self._sweep(key)
        with self._lock:
            self._mem[key] = cfg
        self._persist(key, cfg)
        return cfg

    # -- measured sweep ---------------------------------------------------
    def _time_once(self, fn) -> float:
        t0 = self._timer()
        jax.block_until_ready(fn())
        return self._timer() - t0

    def _sweep(self, key: TuneKey) -> TileConfig:
        """Time the candidate lattice; oracle-gate each candidate against
        the :mod:`repro.kernels.ref` references before it is eligible.
        Any spec/measurement failure degrades to the heuristic config —
        tuning must never take a working dispatch down."""
        spec = _SPECS.get(key.kernel)
        fallback = heuristic_config(key, self.kind)
        if spec is None:
            return fallback
        best_cfg, best_t = None, float("inf")
        try:
            want = None
            for cfg in candidate_lattice(key, self.kind):
                run, reference, exact_flags = spec(key, cfg)
                out = jax.block_until_ready(run())   # compile + oracle leg
                if want is None:
                    want = jax.block_until_ready(reference())
                if not _outputs_match(out, want, exact_flags):
                    continue                          # fast-but-wrong: out
                t = min(self._time_once(run) for _ in range(self.reps))
                if t < best_t:
                    best_cfg, best_t = cfg, t
        except Exception:
            return fallback
        return best_cfg if best_cfg is not None else fallback

    # -- persistence ------------------------------------------------------
    def _load_cache(self) -> Dict[TuneKey, TileConfig]:
        """Winners persisted for this device kind; {} on any problem —
        a missing, corrupt, or partially-written cache file silently
        falls back to heuristics (it will be rewritten on the next
        sweep), never raises into a dispatch."""
        if self.store is None:
            return {}
        try:
            payload = self.store.get_marker(TUNE_NAMESPACE, self.kind)
        except Exception:
            return {}
        out: Dict[TuneKey, TileConfig] = {}
        if not isinstance(payload, dict):
            return out
        for text, entry in payload.get("entries", {}).items():
            try:
                out[TuneKey.decode(text)] = TileConfig.from_dict(entry)
            except Exception:
                continue
        return out

    def _persist(self, key: TuneKey, cfg: TileConfig) -> None:
        if self.store is None:
            return
        with _PERSIST_LOCK:
            entries = {k.encode(): c.as_dict()
                       for k, c in self._load_cache().items()}
            entries[key.encode()] = cfg.as_dict()
            self.store.put_marker(TUNE_NAMESPACE, self.kind, {
                "version": 1,
                "device_kind": self.kind,
                "entries": entries,
            })


# ------------------------------------------------------- ambient tuner knob
_DEFAULT_TUNER = KernelTuner("off")
_TLS = threading.local()


def current() -> KernelTuner:
    """The tuner ops-layer dispatches consult (innermost :func:`use`)."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else _DEFAULT_TUNER


@contextlib.contextmanager
def use(tuner: Optional[KernelTuner]):
    """Install ``tuner`` as the ambient tuner for the calling thread
    (``None`` is a no-op — callers can pass their knob through
    unconditionally)."""
    if tuner is None:
        yield
        return
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(tuner)
    try:
        yield
    finally:
        stack.pop()


def config_for(kernel: str, *, s: int, n: int, r: int = 0,
               dtype: str = "int32") -> TileConfig:
    """Ambient-tuner shorthand the ops wrappers call at dispatch time."""
    return current().config_for(kernel, s=s, n=n, r=r, dtype=dtype)


_SHARED: Dict[Tuple[str, str, str], KernelTuner] = {}
_SHARED_LOCK = threading.Lock()


def shared_tuner(mode: str, store=None,
                 kind: Optional[str] = None) -> Optional[KernelTuner]:
    """Process-wide tuner registry: one tuner per (mode, store root,
    device kind), so repeated sweeps/engine runs share the in-memory
    winners instead of re-reading (or re-measuring) per call. ``"off"``
    maps to ``None`` — nothing to install."""
    if mode is None or mode == "off":
        if mode not in AUTOTUNE_MODES and mode is not None:
            raise ValueError(
                f"autotune mode {mode!r}; one of {AUTOTUNE_MODES}")
        return None
    root = str(getattr(store, "root", ""))
    reg_key = (mode, root, kind or device_kind())
    with _SHARED_LOCK:
        tuner = _SHARED.get(reg_key)
        if tuner is None:
            tuner = KernelTuner(mode, store=store, kind=kind)
            _SHARED[reg_key] = tuner
        return tuner


def tuner_context(autotune: Optional[str], store=None,
                  kind: Optional[str] = None):
    """``with tuning.tuner_context(autotune, store): ...`` — the one-liner
    the engine/controller layers wrap their device legs in. ``"off"`` (or
    ``None``) installs nothing; validation still runs so a typo'd mode
    fails loudly at the knob, not silently as a no-op."""
    return use(shared_tuner(autotune, store=store, kind=kind))


__all__ = [
    "AUTOTUNE_MODES", "DEFAULT_BUCKET_BLOCK", "DEFAULT_CONFIG",
    "DEFAULT_RECORD_TILE", "KERNELS", "KernelTuner", "LANE",
    "MIN_RECORD_TILE", "TUNE_NAMESPACE", "TileConfig", "TuneKey",
    "VMEM_BUDGET_BYTES", "candidate_lattice", "config_for", "current",
    "device_kind", "heuristic_config", "shared_tuner", "tuner_context",
    "use",
]
