"""Reduce a profiler trace (``.xplane.pb``) of the window to device time.

Busy time is the union of the intervals in which an XLA operation ran on a
TPU core (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), clipped
to the window, which is the host annotation ``bench.window`` the harness
wraps around it.  Each operation is attributed to the program (``XLA
Modules``) it ran in.  A kernel is found by its operation's name, which a
Pallas call takes from the jitted function around it: ``_lloyd_step_fused``
(the fused Lloyd step and its assign-only mode), ``_lloyd_solve_batched``
(the S2 stack solve) and ``_init_sweep`` (the k-means|| round).
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import NamedTuple

WINDOW = "bench.window"
#: operations that only hold other operations: left out of per-op sums
CONTROL = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


class Op(NamedTuple):
    name: str        # base name: "%fusion.12 = ..." -> "fusion"
    module: str      # program: "jit_pkmeans(123)" -> "jit_pkmeans"
    start: float     # ns on the trace's clock
    end: float
    text: str        # the head of the HLO text: result type and operands


def _base(text: str) -> str:
    return _SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Reduced:
    """The window's device operations, programs and host annotations."""

    def __init__(self, ops, host, window, chips: int):
        self.ops = ops
        self.host = host
        self.lo, self.hi = window
        self.chips = chips
        self.window_s = (self.hi - self.lo) * 1e-9
        busy = _union(_clip([(o.start, o.end) for o in ops],
                            self.lo, self.hi))
        self.busy_intervals = busy
        self.busy_s = sum(e - s for s, e in busy) * 1e-9 / max(chips, 1)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str, module: str | None = None) -> list[Op]:
        """Operations of kernel ``name`` inside the window, optionally only
        in programs named ``module``."""
        return [o for o in self.ops
                if o.name == name and o.end > self.lo and o.start < self.hi
                and (module is None or o.module == module)]

    @staticmethod
    def seconds(ops) -> float:
        return sum(o.end - o.start for o in ops) * 1e-9

    def module_ops(self, module: str) -> list[Op]:
        return [o for o in self.ops if o.module == module
                and o.name not in CONTROL
                and o.end > self.lo and o.start < self.hi]

    def breakdown(self) -> dict:
        """The ten device operations (by program and name) that took most
        time, and the ten longest idle gaps, each named by the benchmark's
        host annotation that was open at its middle."""
        tot = {}
        for o in self.ops:
            if o.name in CONTROL or o.end <= self.lo or o.start >= self.hi:
                continue
            key = f"{o.module}/{o.name}"
            tot[key] = tot.get(key, 0.0) + (o.end - o.start) * 1e-9
        device_ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        edges = [self.lo] + [x for iv in self.busy_intervals for x in iv] \
            + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [[self.host_at((s + e) / 2), (e - s) * 1e-9]
                for s, e in gaps[:10]]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": idle}

    def host_at(self, t: float) -> str:
        """The innermost benchmark annotation open at ``t``."""
        best = None
        for name, s, e in self.host:
            if s <= t <= e and name != WINDOW and (
                    best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "between benchmark spans"


def reduce_file(path) -> Reduced:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    ops, host = [], []
    chips = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chips += 1
            plane_mods, plane_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    plane_mods = [(e.start_ns, e.start_ns + e.duration_ns,
                                   _MODULE_ID.sub("", e.name))
                                  for e in line.events]
                elif line.name == "XLA Ops":
                    plane_ops = [(e.start_ns, e.duration_ns, e.name[:300])
                                 for e in line.events]
            plane_mods.sort()
            starts = [m[0] for m in plane_mods]
            for s, dur, text in plane_ops:
                i = bisect.bisect_right(starts, s) - 1
                mod = plane_mods[i][2] if i >= 0 and s <= plane_mods[i][1] \
                    else "?"
                ops.append(Op(_base(text), mod, s, s + dur, text))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    if not chips:
        raise ValueError(f"{path}: no TPU plane in the trace")
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"{path}: no {WINDOW} annotation in the trace")
    window = (min(s for s, _ in wins), max(e for _, e in wins))
    return Reduced(ops, host, window, chips)


def reduce_dir(log_dir) -> Reduced:
    """The newest trace the profiler wrote under ``log_dir``."""
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_file(files[-1])


# ------------------------------------------------------------ roofline ----

def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict):
    """Per cent of the least time the chip could take (operations at the
    bf16 peak or bytes at the HBM bandwidth, whichever is longer) in the
    measured ``seconds``, and which of the two bounds it; ``None`` where
    nothing was measured."""
    if seconds <= 0.0 or (flops <= 0.0 and bytes_ <= 0.0):
        return None
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
