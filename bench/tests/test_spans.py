"""The program-span readers on a recorded trace, against hand counts.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

The trace holds the harness's spans, device ops (two of them inside the
radius tile loop's named scopes) and, in the second version, the
program's own spans on two host threads.  Adding the program's spans must
leave every number the trace reducer gives unchanged; the new readers
must give the values counted by hand below (times in us).
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402
import spans  # noqa: E402
import tracereduce  # noqa: E402

US = 1_000_000  # ps


def _events(rows) -> str:
    """rows: (metadata id, start us, end us, {stat id: int}) -> events."""
    out = []
    for mid, a, b, stats in rows:
        st = " ".join(f"stats {{ metadata_id: {k} int64_value: {v} }}"
                      for k, v in stats.items())
        out.append(f"events {{ metadata_id: {mid} offset_ps: {round(a * US)} "
                   f"duration_ps: {round((b - a) * US)} {st} }}")
    return "\n".join(out)


def _meta(names: dict, stats: dict | None = None) -> str:
    out = []
    for mid, name in names.items():
        st = "".join(f' stats {{ metadata_id: {k} {v} }}'
                     for k, v in (stats or {}).get(mid, {}).items())
        out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{name}"{st} }} }}')
    return "\n".join(out)


LOOP = "jit(f)/allpairs.threshold_scan/while"
HITS = LOOP + "/body/closed_call/cond/allpairs.append_hits/cond"

DEVICE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events([(1, 1, 3, {}), (2, 2, 4, {}), (2, 7, 8, {}), (1, 9.5, 11.5, {}),
          (3, 4.5, 5.5, {}), (4, 4.7, 5.1, {})])}
  }}
{_meta({1: "fusion.1", 2: "cabin_build_sparse.3", 3: "while.15",
        4: "cond.9"},
       {3: {1: f'str_value: "{LOOP}"'}, 4: {1: 'ref_value: 2'}})}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "{HITS}" }} }}
}}
"""

HOST = f"""
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events([(1, 0, 10, {}), (2, 4, 7, {}), (3, 8, 9, {}), (4, 0.2, 0.9, {})])}
  }}
"""
HOST_META = {1: "bench.window", 2: "bench.remove", 3: "bench.compact",
             4: "bench.submit"}

# thread B: one flush whose walk runs two rounds; thread C: ingest
PROGRAM = f"""
  lines {{ id: 2 name: "python" timestamp_ns: 0
{_events([(10, 1.0, 9.9, {1: 1}), (11, 1.05, 9.8, {}), (12, 1.1, 9.7, {}),
          (13, 1.15, 9.6, {}), (14, 1.2, 1.3, {}), (15, 1.3, 4.0, {}),
          (16, 4.0, 4.2, {}), (14, 5.6, 6.8, {}), (15, 6.8, 8.0, {}),
          (16, 8.0, 8.1, {}), (17, 9.0, 9.0, {2: 2})])}
  }}
  lines {{ id: 3 name: "python" timestamp_ns: 0
{_events([(18, 3.0, 3.5, {}), (19, 4.1, 4.6, {})])}
  }}
"""
PROGRAM_META = {10: "frontdoor.flush", 11: "engine.topk",
                12: "partition.topk", 13: "allpairs.walk",
                14: "allpairs.walk.plan", 15: "allpairs.walk.score",
                16: "allpairs.walk.merge", 17: "obs.compile",
                18: "engine.add_sparse", 19: "engine.remove"}


def _trace(program: bool) -> str:
    lines = HOST + (PROGRAM if program else "")
    meta = {**HOST_META, **(PROGRAM_META if program else {})}
    return (DEVICE + "planes {\n  id: 2 name: \"/host:CPU\"\n" + lines
            + _meta(meta) + """
  stat_metadata { key: 1 value { id: 1 name: "flush" } }
  stat_metadata { key: 2 value { id: 2 name: "us" } }
}
""")


def _profile(program: bool):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(_trace(program))


class _Ctx:
    def __init__(self, trace, obs=None):
        self.trace = trace
        self.obs = obs


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A run whose trace directory holds the recorded trace."""
    from jax.profiler import ProfileData

    def make(program: bool):
        out = tmp_path / ("with" if program else "without")
        d = out / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(_trace(program)))
        monkeypatch.setattr(run, "TRACE_DIR", out)
        return _Ctx(tracereduce.reduce_dir(str(out), n_devices=1))
    return make


def test_program_spans_leave_the_reducer_unchanged():
    base = tracereduce.reduce_profile(_profile(False))
    full = tracereduce.reduce_profile(_profile(True))
    assert full == base
    # ops [1,4) [4.5,5.5) [7,8) [9.5,10) in the window [0,10)
    assert full.busy_s == pytest.approx(5.5e-6)
    assert full.window_s == pytest.approx(10e-6)
    assert full.idle_gaps[0] == ["bench.remove", pytest.approx(1.5e-6)]
    assert full.idle_gaps[1] == ["bench.compact", pytest.approx(1.5e-6)]
    assert full.idle_gaps[2] == ["bench.submit", pytest.approx(1e-6)]


def test_gaps_are_named_by_the_innermost_program_span():
    w = spans.from_profile(_profile(True), 1)
    assert w.idle_gaps() == [
        ["allpairs.walk.plan", pytest.approx(1.5e-6)],  # [5.5, 7)
        ["allpairs.walk", pytest.approx(1.5e-6)],  # [8, 9.5): walk's self
        ["bench.submit", pytest.approx(1e-6)],  # [0, 1): no program span
        ["engine.remove", pytest.approx(0.5e-6)]]  # [4, 4.5)
    # engine.topk covers 8.75 of the flush's 8.9 us
    assert w.child_cover("frontdoor.flush") == pytest.approx(8.75 / 8.9)
    [walk] = w.named("allpairs.walk")
    assert [c.name for c in walk.children] == [
        "allpairs.walk.plan", "allpairs.walk.score", "allpairs.walk.merge"
    ] * 2
    assert w.named("frontdoor.flush")[0].args == {"flush": 1}


def test_readers_by_hand(recorded):
    ctx = recorded(True)

    def read(name):
        return run.load_reader(name)(ctx)

    # walk less its score children: [1.15,1.3) [4,6.8) [8,9.6) = 4.55 us,
    # one flush ended in the window
    assert read("walk_host_ms.topk") == pytest.approx(4.55e-3)
    # idle inside those: [4,4.5) [5.5,6.8) [8,9.5) = 3.3 of 10 us
    assert read("idle_in_walk.topk") == pytest.approx(33.0)
    # the compile mark at 9 us closes a 2 us phase
    assert read("compile_s.topk") == pytest.approx(2e-6)
    assert read("compile_s.radius") == pytest.approx(2e-6)
    # append_hits [4.7,5.1) of the loop's [4.5,5.5)
    assert read("hit_extract_share.radius") == pytest.approx(40.0)
    # add_sparse [3,3.5) is busy; remove [4.1,4.6) idles until 4.5
    assert read("idle_in_engine.ingest") == pytest.approx(4.0)


def test_readers_report_nothing_without_program_spans(recorded):
    ctx = recorded(False)
    for name in ("walk_host_ms.topk", "idle_in_walk.topk", "compile_s.topk",
                 "compile_s.radius", "hit_extract_share.radius",
                 "idle_in_engine.ingest"):
        assert run.load_reader(name)(ctx) is None, name
    assert run.load_reader("walk_host_ms.topk")(_Ctx(None)) is None


def test_walk_rounds_reader_reads_window_differences():
    from repro.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("index_walk_rounds_total").inc(5)
    reg.counter("frontdoor_flushes_total").inc(1)
    delta = run.ObsDelta(reg)
    reg.counter("index_walk_rounds_total").inc(24)
    reg.counter("frontdoor_flushes_total").inc(3)
    delta.close()
    read = run.load_reader("walk_rounds.topk")
    assert read(_Ctx(None, delta)) == pytest.approx(8.0)
    old = MetricsRegistry()  # a program without the rounds counter
    old.counter("frontdoor_flushes_total").inc(3)
    delta = run.ObsDelta(old)
    delta.close()
    assert read(_Ctx(None, delta)) is None


def test_interval_arithmetic():
    assert spans.union([[3, 4], [1, 2], [1.5, 3]]) == [[1, 4]]
    assert spans.intersect([[0, 2], [3, 5]], [[1, 4]]) == [[1, 2], [3, 4]]
    assert spans.subtract([[0, 10]], [[1, 2], [4, 5], [9, 12]]) == [
        [0, 1], [2, 4], [5, 9]]
    assert spans.length([[0, 1e9], [2e9, 2.5e9]]) == pytest.approx(1.5)
