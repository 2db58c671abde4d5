"""The program's spans in a traced window, on the profiler's clock.

The program records its spans with `jax.profiler.TraceAnnotation`
(`repro.obs.span`), so under the harness's profiler session they land in
the window's `.xplane.pb` beside the device ops, on one clock.  This
module reads three things from that file for the readers that time the
program's own layers:

  * program spans: host events named `<module>.<what>` by a module of the
    program (PROGRAM).  Nesting on each host thread gives every span its
    children; its self time is its interval less what they cover.
    Instants (INSTANTS) are points, not children.
  * device ops: the `XLA Ops` of the cell's devices, each with the scope
    path XLA keeps from `jax.named_scope` (the event metadata's `tf_op`
    stat).  ProfileData does not expose event-metadata stats, so the path
    is read from the same file through a subset of the XPlane schema
    (`_xspace_class`).
  * compile stalls: each `obs.compile` instant closes a JAX compile phase
    of `us` microseconds.

`window(ctx)` is None without a trace, or when the window holds no
program span (a program that does not record on the profiler's clock):
the readers built on it then report nothing.  Nested intervals count
once: every total here is a length of a union, never a sum of spans.
"""

from __future__ import annotations

import functools
import os
import warnings
from dataclasses import dataclass, field

import tracereduce

PROGRAM = ("frontdoor.", "engine.", "allpairs.", "partition.", "store.",
           "migrate.", "merge_tree.", "ingest.", "cluster.", "obs.",
           "crash_point")
INSTANTS = ("obs.compile", "crash_point")
COMPILE = "obs.compile"
BENCH = tracereduce.HOST_SPAN_PREFIX


# ---------------------------------------------------------------------------
# intervals: sorted lists of disjoint [a, b) pairs, in ns
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    """Seconds covered by disjoint intervals."""
    return sum(b - a for a, b in intervals) / 1e9


def intersect(xs, ys) -> list:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list:
    """xs less ys (both disjoint and sorted)."""
    out = []
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if b > a:
            out.append([a, b])
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    args: dict
    children: list = field(default_factory=list)

    def self_intervals(self, minus: tuple | None = None) -> list:
        """The span's interval less what its children (those named in
        `minus`, or all) cover."""
        kids = union([c.t0, c.t1] for c in self.children
                     if minus is None or c.name in minus)
        return subtract([[self.t0, self.t1]], kids)


@dataclass
class Window:
    w0: float
    w1: float
    spans: list  # program Spans, every thread, with their children
    bench: list  # (name, t0, t1) of the harness's own spans
    busy: list  # per device: union of its op intervals in the window
    ops: list  # per device: [(op name, t0, t1)] clipped to the window
    scopes: dict  # op name -> scope path (tf_op)

    @property
    def seconds(self) -> float:
        return (self.w1 - self.w0) / 1e9

    def clip(self, intervals) -> list:
        return intersect(union(intervals), [[self.w0, self.w1]])

    def named(self, *names) -> list:
        return [s for s in self.spans if s.name in names]

    def ended(self, name: str) -> int:
        """Spans named `name` that end inside the window."""
        return sum(1 for s in self.named(name) if self.w0 <= s.t1 <= self.w1)

    def covered(self, *names) -> list:
        """Union of the spans named `names`, clipped to the window."""
        return self.clip([s.t0, s.t1] for s in self.named(*names))

    def self_time(self, name: str, minus: tuple | None = None) -> list:
        """Union of the self intervals of the spans named `name` (less
        their children named in `minus`, or all), clipped to the window."""
        return self.clip(iv for s in self.named(name)
                         for iv in s.self_intervals(minus))

    def idle_in(self, intervals) -> float:
        """Device-idle seconds inside `intervals` (disjoint, sorted),
        averaged over the devices."""
        total = length(intervals)
        return sum(total - length(intersect(intervals, b))
                   for b in self.busy) / len(self.busy)

    def device_seconds(self, scope: str) -> float:
        """Seconds of the union of the device ops whose scope path holds
        `scope` as a component, summed over the devices."""
        return sum(length(union(
            [a, b] for nm, a, b in dev
            if scope in self.scopes.get(nm, "").split("/")))
            for dev in self.ops)

    def compile_seconds(self) -> float:
        """Seconds of the union of JAX's compile phases in the window."""
        return length(self.clip(
            [s.t0 - 1e3 * int(s.args.get("us", 0)), s.t0]
            for s in self.named(COMPILE)))

    def child_cover(self, name: str) -> float:
        """Share of the summed duration of the spans named `name` that
        their children cover."""
        spans = [s for s in self.named(name)
                 if self.w0 <= s.t0 and s.t1 <= self.w1]
        total = sum(s.t1 - s.t0 for s in spans)
        kids = sum(1e9 * length(union([c.t0, c.t1] for c in s.children))
                   for s in spans)
        return kids / total if total else 0.0

    def self_seconds(self, n: int = 15) -> list:
        """[(span name, self seconds in the window)], the n largest."""
        out: dict = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + length(
                self.clip(s.self_intervals()))
        return sorted(out.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The n longest device-idle gaps of the window, [name, seconds],
        each named by the program span whose self time covers most of it,
        else by the harness span that does, else "no host span"."""
        gaps = []
        for busy in self.busy:
            gaps += subtract([[self.w0, self.w1]], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        own = [(s.name, s.self_intervals()) for s in self.spans]
        out = []
        for a, b in gaps[:n]:
            best, cover = None, 0.0
            for name, ivs in own:
                c = length(intersect(ivs, [[a, b]]))
                if c > cover:
                    best, cover = name, c
            if best is None:
                best = "no host span"
                for name, s0, s1 in self.bench:
                    c = min(b, s1) - max(a, s0)
                    if c > cover:
                        best, cover = name, c
            out.append([best, (b - a) / 1e9])
        return out


def _nest(events: list) -> list:
    """Spans of one host thread, [(name, t0, t1, args)], as Spans whose
    children are the spans nested directly inside them."""
    out, stack = [], []
    for name, t0, t1, args in sorted(events, key=lambda e: (e[1], -e[2])):
        span = Span(name, t0, t1, args)
        out.append(span)
        if name.startswith(INSTANTS):
            continue
        while stack and not (stack[-1].t0 <= t0 and t1 <= stack[-1].t1):
            stack.pop()
        if stack:
            stack[-1].children.append(span)
        stack.append(span)
    return out


def from_profile(profile, n_devices: int | None = None,
                 scopes: dict | None = None) -> Window | None:
    """The Window of a ProfileData (anything with its planes/lines/events
    shape); `scopes` maps device op names to their scope paths.  None
    when the window holds no program span."""
    window, bench, threads, devices = None, [], [], {}
    with warnings.catch_warnings():
        # jaxlib's event-stats iterator warns on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in profile.planes:
            if plane.name.startswith(tracereduce.DEVICE_PREFIX):
                ops = []
                for line in plane.lines:
                    if line.name == tracereduce.OPS_LINE:
                        ops += [(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events]
                dev = int(plane.name[len(tracereduce.DEVICE_PREFIX):])
                devices[dev] = ops
                continue
            for line in plane.lines:
                mine = []
                for ev in line.events:
                    nm = ev.name
                    t0, t1 = ev.start_ns, ev.start_ns + ev.duration_ns
                    if nm.startswith(PROGRAM):
                        mine.append((nm, t0, t1, dict(ev.stats)))
                    elif nm == tracereduce.WINDOW_SPAN:
                        window = (t0, t1)
                    elif nm.startswith(BENCH):
                        bench.append((nm, t0, t1))
                if mine:
                    threads.append(mine)
    if window is None or not devices:
        return None
    w0, w1 = window
    spans = [s for t in threads for s in _nest(t)]
    if not any(s.t1 > w0 and s.t0 < w1 for s in spans):
        return None
    ids = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    ops = [[(nm, max(a, w0), min(b, w1)) for nm, a, b in devices[d]
            if b > w0 and a < w1] for d in ids]
    busy = [union([a, b] for _, a, b in dev) for dev in ops]
    return Window(w0, w1, spans, bench, busy, ops, scopes or {})


# ---------------------------------------------------------------------------
# scope paths, from the event metadata of the device planes
# ---------------------------------------------------------------------------


@functools.cache
def _xspace_class():
    """XSpace message class for the subset of tsl's xplane.proto read here
    (field numbers as upstream; every other field is skipped unread)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench/xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    shapes = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64, None),
                  ("str_value", 5, F.TYPE_STRING, None),
                  ("ref_value", 7, F.TYPE_UINT64, None)],
        "XEventMetadata": [("id", 1, F.TYPE_INT64, None),
                           ("name", 2, F.TYPE_STRING, None),
                           ("stats", 5, F.TYPE_MESSAGE, "XStat")],
        "XStatMetadata": [("id", 1, F.TYPE_INT64, None),
                          ("name", 2, F.TYPE_STRING, None)],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                               ("value", 2, F.TYPE_MESSAGE,
                                "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64, None),
                              ("value", 2, F.TYPE_MESSAGE, "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_STRING, None),
                   ("event_metadata", 4, F.TYPE_MESSAGE,
                    "EventMetadataEntry"),
                   ("stat_metadata", 5, F.TYPE_MESSAGE,
                    "StatMetadataEntry")],
        "XSpace": [("planes", 1, F.TYPE_MESSAGE, "XPlane")],
    }
    repeated = {("XEventMetadata", "stats"), ("XPlane", "event_metadata"),
                ("XPlane", "stat_metadata"), ("XSpace", "planes")}
    for msg, fields in shapes.items():
        m = fd.message_type.add(name=msg)
        for name, num, typ, ref in fields:
            f = m.field.add(name=name, number=num, type=typ,
                            label=(F.LABEL_REPEATED
                                   if (msg, name) in repeated
                                   else F.LABEL_OPTIONAL))
            if ref:
                f.type_name = f".bench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(raw: bytes) -> dict:
    """{device op name: scope path} from an `.xplane.pb`'s bytes: the
    `tf_op` stat of each event metadata on the device planes."""
    out: dict = {}
    for plane in _xspace_class().FromString(raw).planes:
        if not plane.name.startswith(tracereduce.DEVICE_PREFIX):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in names.items() if v == "tf_op"}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in tf_op:
                    out[e.value.name] = (st.str_value
                                         or names.get(st.ref_value, ""))
    return out


# ---------------------------------------------------------------------------
# the run's window
# ---------------------------------------------------------------------------


_last: tuple | None = None  # ((path, mtime, n_devices), Window | None)


def load(path: str, n_devices: int | None = None) -> Window | None:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return from_profile(ProfileData.from_serialized_xspace(raw), n_devices,
                        op_scopes(raw))


def window(ctx) -> Window | None:
    """The Window of the run's traced window (parsed once a run), or None
    without a trace or a program span."""
    global _last
    if ctx.trace is None:
        return None
    import run
    path = tracereduce.latest_xplane(str(run.TRACE_DIR))
    key = (path, os.path.getmtime(path), ctx.trace.n_devices)
    if _last is None or _last[0] != key:
        _last = (key, load(path, ctx.trace.n_devices))
    return _last[1]


def last() -> Window | None:
    """The Window the last `window` call parsed (bench/breakdown.py reads
    it after the run's trace is gone)."""
    return None if _last is None else _last[1]


def compile_seconds(ctx):
    """The compile_s readers: seconds of the union of JAX's compile phases
    in the traced window, as the program's `obs.compile` instants mark
    them; None without program spans."""
    w = window(ctx)
    return None if w is None else w.compile_seconds()
