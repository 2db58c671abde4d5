"""Reduce a profiler trace of the measured window to numbers.

Input: the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`.  The window is the host span the harness
names WINDOW_SPAN.  Device planes are those named `/device:TPU:<n>`;
their op events are on the line named OPS_LINE.  From those:

  * busy_s — per device, the union of op intervals inside the window,
    averaged over the devices used; idle share = 1 - busy / window;
  * op_seconds — device seconds per op name (summed, clipped to the
    window), for the kernels' rooflines and the breakdown;
  * idle_gaps — the longest gaps between device ops inside the window,
    each named by the harness span that covers most of it on the host.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: dict = field(default_factory=dict)  # name -> device seconds
    op_counts: dict = field(default_factory=dict)  # name -> events
    idle_gaps: list = field(default_factory=list)  # [(host span, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_events(self, fragment: str) -> list:
        """[(op text, events, device seconds)] of ops naming `fragment`."""
        return [(k, self.op_counts[k], v) for k, v in self.op_seconds.items()
                if fragment in k]

    def top_ops(self, n: int = 10) -> list:
        """The n ops with the most device time, by short name."""
        merged: dict = {}
        for k, v in self.op_seconds.items():
            s = short_name(k)
            merged[s] = merged.get(s, 0.0) + v
        return sorted(merged.items(), key=lambda kv: -kv[1])[:n]


def idle_percent(ctx):
    """The device_idle readers: share of the traced window in which no
    operation ran on the device (1 - union of device-op intervals /
    window), in percent; None without a trace."""
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP = re.compile(r"^(%\S+) = (\([^()]*\)|\S+) ([\w-]+)\(")


def short_name(op_text: str) -> str:
    """`%name = shape{layout} kind(...)...` -> `%name kind shape`."""
    m = _OP.match(_LAYOUT.sub("", op_text))
    if not m:
        return op_text[:120]
    return f"{m[1]} {m[3]} {m[2]}"[:120]


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_profile(profile, n_devices: int | None = None,
                   n_gaps: int = 10) -> Reduced:
    """Reduce a `ProfileData` (or anything with the same planes/lines/
    events shape).  `n_devices` limits the average to the first devices
    (the chips the cell asked for)."""
    window = None
    host_spans: list = []
    devices: dict = {}
    for plane in profile.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns, ev.start_ns
                                + ev.duration_ns) for ev in line.events)
            devices[int(name[len(DEVICE_PREFIX):])] = ops
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = span
                else:
                    host_spans.append(span)
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("trace has no device plane")
    w0, w1 = window[1], window[2]
    ids = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    op_s: dict = {}
    op_n: dict = {}
    busy = 0.0
    gaps: list = []
    for dev in ids:
        clipped = [(nm, max(a, w0), min(b, w1)) for nm, a, b in devices[dev]
                   if b > w0 and a < w1]
        for nm, a, b in clipped:
            op_s[nm] = op_s.get(nm, 0.0) + (b - a) / 1e9
            op_n[nm] = op_n.get(nm, 0) + 1
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged) / 1e9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:n_gaps]:
        best, cover = "no host span", 0
        for nm, s0, s1 in host_spans:
            c = min(b, s1) - max(a, s0)
            if c > cover:
                best, cover = nm, c
        named.append([best, (b - a) / 1e9])
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy / len(ids),
                   n_devices=len(ids), op_seconds=op_s, op_counts=op_n,
                   idle_gaps=named)


def reduce_dir(trace_dir: str, n_devices: int | None = None) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(latest_xplane(trace_dir)),
                          n_devices=n_devices)
