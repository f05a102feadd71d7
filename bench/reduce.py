"""From a profiler trace to the numbers the per-layer metrics read.

A run with ``--trace 1`` records one trace of its measured window with
``jax.profiler``. ``reduce_trace`` turns it into a :class:`Reduced`:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices (``jax.profiler.ProfileData``: the
  ``XLA Ops`` line of each ``/device:TPU:n`` plane; on the CPU, the host
  events that carry an ``hlo_op``);
* ``window_s``: the traced window, from the profile's start and stop;
* ``ops``: device self time per HLO op with the op's framework name,
  the ``jax.named_scope`` path its instruction was traced under — so
  ``scope_seconds("kpynq/candidate_pass")`` is the device time of that
  phase, whatever its ops are called. The op table is xprof's
  ``hlo_stats``, which joins the device events with the programs' HLO;
* ``spans``: the benchmark's own host spans (``bench.*``
  ``TraceAnnotation``s) and ``gaps``, the device's idle intervals, so
  each gap can be put down to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    ops: list            # [(hlo op, framework op name, self seconds)]
    spans: list          # [(name, start_ns, end_ns)]
    gaps: list           # [(start_ns, end_ns)] device idle, longest first

    def scope_seconds(self, scope: str) -> float | None:
        """Device self time of every op traced under ``scope`` (a
        ``named_scope`` path such as ``kpynq/candidate_pass``); None when
        the trace names no op at all (no op table)."""
        if not self.ops:
            return None
        return sum(t for _, name, t in self.ops
                   if f"/{scope}/" in f"/{name}")

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> list:
        """``[[name, seconds], ...]``: the ops with the most self time."""
        ranked = sorted(self.ops, key=lambda o: -o[2])[:n]
        return [[f"{name or '(unnamed)'} [{hlo}]", t] for hlo, name, t in ranked]

    def idle_gaps(self, n: int = 10) -> list:
        """``[[label, seconds], ...]``: the longest idle gaps, each named
        by the host span that overlaps it most (``host`` where none
        does)."""
        out = []
        for lo, hi in self.gaps[:n]:
            best, label = 0, "host"
            for name, s, e in self.spans:
                ov = min(hi, e) - max(lo, s)
                if ov > best:
                    best, label = ov, name
            out.append([label, (hi - lo) * 1e-9])
        return out


def _union_ns(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _device_intervals(pd):
    """Per device, the intervals of its ops."""
    devices = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [(e.start_ns, e.end_ns)
                                           for e in line.events]
    if devices:
        return devices
    for plane in pd.planes:                    # the CPU backend
        if plane.name == "/host:CPU":
            ivs = [(e.start_ns, e.end_ns) for line in plane.lines
                   for e in line.events if "hlo_op" in dict(e.stats)]
            if ivs:
                devices["cpu"] = ivs
    return devices


def _window_ns(pd, fallback):
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                return int(st["profile_stop_time"]) - int(st["profile_start_time"])
    return fallback


def _hlo_ops(path):
    """xprof's ``hlo_stats`` table as ``[(hlo op, framework name,
    self seconds)]``; empty where xprof finds no device ops. xprof leaves
    an ``ALL_HOSTS.op_stats.pb`` beside the trace."""
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    if not data:
        return []
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    ops = []
    for row in table.get("rows", []):
        v = dict(zip(cols, (c.get("v") for c in row["c"])))
        ops.append((v["hlo_op_name"], (v.get("tf_op_name") or "").rstrip(":"),
                    float(v["total_self_time"]) * 1e-6))
    return ops


def trace_file(trace_dir) -> str:
    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(found)}")
    return found[0]


def reduce_trace(path) -> Reduced:
    """Reduce one ``.xplane.pb`` file (or a directory holding one)."""
    from jax.profiler import ProfileData
    path = str(path)
    if os.path.isdir(path):
        path = trace_file(path)
    pd = ProfileData.from_file(path)
    per_device = _device_intervals(pd)
    if not per_device:
        raise ValueError(f"{path}: no device operation in the trace")
    merged = {d: _union_ns(iv) for d, iv in per_device.items()}
    busy_ns = sum(sum(e - s for s, e in m) for m in merged.values()) \
        / len(merged)
    first = min(m[0][0] for m in merged.values())
    last = max(m[-1][1] for m in merged.values())
    window_ns = _window_ns(pd, last - first)
    # idle gaps of the first device (one chip per cell)
    m = merged[sorted(merged)[0]]
    gaps = sorted(((a[1], b[0]) for a, b in zip(m, m[1:]) if b[0] > a[1]),
                  key=lambda g: g[0] - g[1])
    spans = [(e.name, e.start_ns, e.end_ns) for plane in pd.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return Reduced(busy_s=busy_ns * 1e-9, window_s=window_ns * 1e-9,
                   ops=_hlo_ops(path), spans=spans, gaps=gaps)
