"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

- the window: from the first to the last harness span (``sweep``,
  ``reset``) on the host;
- device busy time: the union of the intervals in which an XLA operation
  ran on a device, clipped to the window, averaged over the devices;
- device time per XLA module (a jitted function or a Pallas kernel's
  program, named ``jit_<function>`` by JAX), summed over its operations;
- the longest idle gaps on the device, each named by the harness span and
  the innermost host event on the same host thread at the gap's middle.

Only ``jax`` is needed to read the file (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

HARNESS_SPANS = ("sweep", "reset")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       #: averaged over the devices
    n_devices: int
    module_s: Dict[str, float]          #: module name -> device seconds
    module_calls: Dict[str, int]        #: module name -> executions
    gaps: List[Tuple[str, float]]       #: longest idle gaps, named
    spans: Dict[str, int]               #: harness span -> count

    def kernel_s(self, patterns: Sequence[str]) -> float:
        """Device seconds of every module whose name matches a pattern."""
        return sum(s for m, s in self.module_s.items()
                   if any(re.search(p, m) for p in patterns))

    def kernel_calls(self, patterns: Sequence[str]) -> int:
        return sum(c for m, c in self.module_calls.items()
                   if any(re.search(p, m) for p in patterns))

    def top_modules(self, k: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.module_s.items(), key=lambda x: -x[1])[:k]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> Dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _module_name(raw: str) -> str:
    return re.sub(r"\(\d+\)$", "", str(raw))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def reduce(path: str, n_gaps: int = 10) -> TraceSummary:
    from jax._src.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), n_gaps)


def reduce_profile(pd, n_gaps: int = 10) -> TraceSummary:
    """:func:`reduce` of an already loaded ``ProfileData``."""
    import bisect

    planes = list(pd.planes)

    # ---- host: the harness spans define the window
    spans: List[Tuple[str, float, float, object]] = []
    host_lines = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            mine = [(e.name, e.start_ns, e.start_ns + e.duration_ns, line)
                    for e in events if e.name in HARNESS_SPANS]
            if mine:
                spans += mine
                host_lines.append((line, events))
    if not spans:
        raise ValueError("trace holds no harness span (sweep/reset)")
    w0 = min(s[1] for s in spans)
    w1 = max(s[2] for s in spans)

    # ---- devices: operations, grouped by module
    module_ns: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    busy_ns, n_dev, all_busy = 0.0, 0, []
    for plane in planes:
        if not _is_device_plane(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        mods = lines.get("XLA Modules")
        if ops is None:
            continue
        n_dev += 1
        mod_iv = []
        if mods is not None:
            for e in mods.events:
                name = _module_name(e.name)
                mod_iv.append((e.start_ns, e.start_ns + e.duration_ns, name))
                if w0 <= e.start_ns <= w1:
                    module_calls[name] = module_calls.get(name, 0) + 1
        mod_iv.sort()
        starts = [m[0] for m in mod_iv]
        ivs = []
        for e in ops.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            st = _stats(e)
            name = st.get("hlo_module")
            if name is None and mod_iv:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and mod_iv[i][1] >= e.start_ns:
                    name = mod_iv[i][2]
            name = _module_name(name or "unattributed")
            module_ns[name] = module_ns.get(name, 0.0) + (b - a)
        u = _union(ivs)
        busy_ns += sum(b - a for a, b in u)
        all_busy.append(u)
    if n_dev == 0:
        raise ValueError("trace holds no device operations")

    # ---- idle gaps of the first device, named by what the host did
    gaps = []
    busy0 = all_busy[0]
    edges = [w0] + [x for iv in busy0 for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    named = []
    for a, b in gaps[:n_gaps]:
        named.append((_host_doing((a + b) / 2, host_lines), (b - a) / 1e9))
    counts: Dict[str, int] = {}
    for s in spans:
        counts[s[0]] = counts.get(s[0], 0) + 1
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / n_dev / 1e9,
        n_devices=n_dev,
        module_s={k: v / 1e9 for k, v in module_ns.items()},
        module_calls=module_calls, gaps=named, spans=counts)


def _host_doing(t: float, host_lines) -> str:
    """``<harness span>/<innermost host event>`` at time ``t``."""
    for _line, events in host_lines:
        inside = [e for e in events
                  if e.start_ns <= t <= e.start_ns + e.duration_ns]
        if not inside:
            continue
        span = next((e.name for e in inside if e.name in HARNESS_SPANS),
                    None)
        if span is None:
            continue
        inner = min(inside, key=lambda e: e.duration_ns)
        if inner.name == span:
            return span
        return f"{span}/{inner.name}"
    return "outside"


def summary_lines(ts: Optional[TraceSummary]) -> List[str]:
    if ts is None:
        return []
    out = [f"trace: window {ts.window_s:.3f} s, device busy "
           f"{ts.busy_s:.3f} s on {ts.n_devices} device(s), spans "
           f"{ts.spans}"]
    for name, s in ts.top_modules(25):
        out.append(f"trace module {name}: {s:.6f} s in "
                   f"{ts.module_calls.get(name, 0)} call(s)")
    for name, s in ts.gaps:
        out.append(f"trace gap {name}: {s:.6f} s")
    return out
