"""Self-tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

Each cell runs end to end (set-up, warm-up, window, reference check) on a
shrunken copy of its configuration; the controls and planted faults its
cell class names must come out not correct; a kind the harness has never
seen runs from a file of its own; the trace reducer and the roofline byte
counts are checked against hand counts; and bench/run.py refuses to run
off a TPU.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import reference as ref  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402
import tracereduce  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SECONDS = 0.5


def cell_class(workload: str):
    return run.cell_class(run.cell_files(run.load_spec(), workload)[2]["kind"])


def tiny(workload: str, **traffic_over):
    """The cell's files, shrunk by its cell class."""
    spec = run.load_spec()
    _, cfg, traffic = run.cell_files(spec, workload)
    cfg, traffic = cell_class(workload).tiny(copy.deepcopy(cfg),
                                             copy.deepcopy(traffic))
    traffic.update(traffic_over)
    return spec, cfg, traffic


def run_tiny(workload: str, seed: int = 5, controls: tuple = (),
             trace: bool = False, **traffic_over):
    spec, cfg, traffic = tiny(workload, **traffic_over)
    return run.run_cell(workload, seed, SECONDS, trace, spec=spec,
                        device=CPU, cfg=cfg, traffic=traffic,
                        controls=controls, log=lambda *a: None)


WORKLOADS = [w["name"] for w in run.load_spec()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_correct_end_to_end(workload):
    res = run_tiny(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = run.load_spec()
    want = {m["name"] for m in run.cell_metrics(spec, workload, False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def _controls():
    """(workload, control) for every control a cell's class names."""
    return [(w, c) for w in WORKLOADS for c in cell_class(w).CONTROLS]


@pytest.mark.parametrize("workload,control", _controls())
def test_control_is_not_correct(workload, control):
    res = run_tiny(workload, controls=(control,))
    assert res["correct"], res["checks"]
    assert not res["controls"][control]["correct"], res["controls"]


def _faults():
    """(workload, fault) for every fault a cell's class names (bench/
    faults.py plants the program's)."""
    return [(w, f) for w in WORKLOADS for f in cell_class(w).FAULTS]


@pytest.mark.parametrize("workload,fault", _faults())
def test_planted_fault_is_not_correct(monkeypatch, workload, fault):
    cell_class(workload).FAULTS[fault](monkeypatch)
    res = run_tiny(workload, check_sample=10_000)
    assert not res["correct"], res["checks"]


TOY_CELL = '''
import time

import numpy as np


def answer(x):
    return x * x


def plant_wrong_square(monkeypatch):
    import cell_toy_square
    monkeypatch.setattr(cell_toy_square, "answer", lambda x: x * x + 1)


class Cell:
    CONTROLS = ("off_by_one",)
    FAULTS = {"wrong_square": plant_wrong_square}
    OFFERS_RATE = False

    def __init__(self, workload, cfg, traffic, seed, clock, span):
        self.chips = workload["chips"]
        self.n = int(traffic["requests"])
        self.seed = seed

    @classmethod
    def tiny(cls, cfg, traffic):
        return cfg, dict(traffic, requests=50)

    def setup(self):
        self.xs = np.random.default_rng(self.seed).integers(0, 1000, self.n)

    def obs(self):
        return self

    def snapshot(self):
        return {}

    def window(self, ctx, seconds):
        t0 = time.perf_counter()
        self.out, recs = [], []
        for x in self.xs:
            t = time.perf_counter()
            self.out.append(answer(int(x)))
            recs.append((t, time.perf_counter() + 1e-6, True))
        ctx.requests["square"] = recs
        return t0, recs[-1][1], len(recs), 0

    def release(self):
        pass

    def check(self, controls=()):
        want = self.xs.astype(np.int64) ** 2

        def wrong(got):
            return int(np.count_nonzero(np.asarray(got) != want))
        out = {"program": {"squares_wrong": (wrong(self.out), 0),
                           "chips_wrong": (int(self.chips != 1), 0)}}
        if "off_by_one" in controls:
            out["off_by_one"] = {"squares_wrong": (wrong(want + 1), 0)}
        return out
'''


def test_a_new_kind_runs_from_files_alone(tmp_path, monkeypatch):
    """A kind, a reader and a cell the harness has never seen, each in a
    file of its own: the run reports the reader's metric and the kind's
    checks, its control and its planted fault come out not correct."""
    (tmp_path / "cell_toy_square.py").write_text(TOY_CELL)
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "squares_per_s.py").write_text(textwrap.dedent('''
        def read(ctx):
            return ctx.rate("square")
    '''))
    (metrics / "setup_s.py").write_text(
        (run.METRICS / "setup_s.py").read_text())
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(run, "METRICS", metrics)
    spec = copy.deepcopy(run.load_spec())
    spec["workloads"].append({"name": "toy", "config": "toy",
                              "traffic": "toy", "chips": 1, "why": "toy"})
    spec["end_to_end"].append({"name": "squares_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock", "workloads": ["toy"]})
    cls = run.cell_class("toy_square")
    cfg, traffic = cls.tiny({}, {"kind": "toy_square", "requests": 10**6})

    def go(controls=()):
        return run.run_cell("toy", 7, SECONDS, False, spec=spec, device=CPU,
                            cfg=cfg, traffic=traffic, controls=controls,
                            log=lambda *a: None)

    res = go(controls=cls.CONTROLS)
    assert res["correct"] and res["attempted"] == 50 and res["failed"] == 0
    assert set(res["metrics"]) == {"squares_per_s", "setup_s"}
    assert res["metrics"]["squares_per_s"]["value"] > 0
    assert res["checks"] == {"squares_wrong": {"value": 0, "limit": 0},
                             "chips_wrong": {"value": 0, "limit": 0}}
    assert res["controls"]["off_by_one"]["squares_wrong"]["value"] == 50
    assert not res["controls"]["off_by_one"]["correct"]
    cls.FAULTS["wrong_square"](monkeypatch)
    res = go()
    assert not res["correct"]
    assert res["checks"]["squares_wrong"]["value"] == 50


def test_arrivals_are_the_same_multiset_for_every_seed():
    from cell_open_loop import OpenLoad
    a = OpenLoad(None, "topk", 10, None, 46.0, 1, None).due_offsets(20.0, False)
    b = OpenLoad(None, "topk", 10, None, 46.0, 2**31 + 9, None).due_offsets(
        20.0, False)
    assert len(a) == len(b) == 920
    assert a[0] == b[0] == 0.0 and a[-1] < 20.0 and b[-1] < 20.0
    ga, gb = np.diff(np.append(a, 20.0)), np.diff(np.append(b, 20.0))
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", WORKLOADS[0],
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == run.EXIT_NO_CHIP
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_run_fails_in_a_checkout_without_the_program(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("BENCHMARK.json",):
        (tmp_path / f).write_text((ROOT / f).read_text())
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


# ---------------------------------------------------------------------------
# the yardstick's pieces
# ---------------------------------------------------------------------------

_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "cabin_build_sparse.3" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.remove" } }
  event_metadata { key: 3 value { id: 3 name: "bench.compact" } }
}
"""


def test_trace_reducer_on_a_recorded_trace():
    from jax.profiler import ProfileData
    t = tracereduce.reduce_profile(ProfileData.from_text_proto(_TRACE))
    # window [0, 10) us; ops [1,3) [2,4) [7,8) [9.5,11.5) -> busy 1+2+1+.5
    assert t.window_s == pytest.approx(10e-6)
    assert t.busy_s == pytest.approx(4.5e-6)
    assert t.idle_share == pytest.approx(0.55)
    [(text, n, secs)] = t.kernel_events("cabin_build_sparse")
    assert (text, n, secs) == ("cabin_build_sparse.3", 2, pytest.approx(3e-6))
    assert t.op_seconds["fusion.1"] == pytest.approx(2.5e-6)
    # gaps: [4,7) 3us under bench.remove, [8,9.5) under bench.compact,
    # [0,1) under no span
    assert t.idle_gaps[0] == ["bench.remove", pytest.approx(3e-6)]
    assert t.idle_gaps[1] == ["bench.compact", pytest.approx(1.5e-6)]
    assert t.idle_gaps[2] == ["no host span", pytest.approx(1e-6)]


SKETCH_OP = (
    "%cabin_build_sparse.1 = s32[8192,128]{1,0:T(8,128)} custom-call("
    "s32[8192,1024]{1,0:T(8,128)} %indices.1, s32[8192,1024]{1,0:T(8,128)} "
    "%values.1), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={s32[8192,1024]{1,0}, s32[8192,1024]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_roofline_bytes_by_hand():
    # the sketch kernel's op as a v5e trace names it: 8,192 rows of 1,024
    # int32 indices and values read, 8,192 packed 4096-bit sketches written
    assert roofline.custom_call_bytes(SKETCH_OP) == (
        8192 * 1024 * 4 + 8192 * 1024 * 4 + 8192 * 512)
    assert tracereduce.short_name(SKETCH_OP) == \
        "%cabin_build_sparse.1 custom-call s32[8192,128]"
    # a kernel with two outputs and a scalar operand
    op = ("%topk_select.2 = (s32[64,10]{1,0}, s32[64,10]{1,0}) custom-call("
          "s32[1,1]{1,0} %m, s32[64,128]{1,0} %q, s32[64,1]{1,0} %wq, "
          "s32[2048,128]{1,0} %b, s32[1,2048]{1,0} %wb), custom_call_target="
          "\"tpu_custom_call\"")
    assert roofline.custom_call_bytes(op) == 4 * (
        2 * 64 * 10 + 1 + 64 * 128 + 64 + 2048 * 128 + 2048)
    assert roofline.least_seconds(819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")


def test_reference_sketch_agrees_with_the_program_and_itself():
    import jax.numpy as jnp
    from repro.core.cabin import CabinParams, sketch_sparse_jit
    import corpus as corpus_mod
    spec, cfg, _ = tiny(WORKLOADS[0])
    c = corpus_mod.corpus_of(cfg)
    idx, val = corpus_mod.Stream(c, 3, 0).batch(0, 64)
    p = CabinParams.create(c.n_dims, 512, seed=3)
    want = ref.np_sketch(512, p.psi_seed, p.pi_seed, np.asarray(idx),
                         np.asarray(val))
    bits = ref.ref_bits(idx, val, d=512, psi_seed=p.psi_seed,
                        pi_seed=p.pi_seed)
    assert np.array_equal(np.asarray(ref.pack_bits(bits)), want)
    assert np.array_equal(np.asarray(sketch_sparse_jit(
        p, jnp.asarray(idx), jnp.asarray(val))), want)
    cheap = ref.np_sketch(512, p.psi_seed, p.pi_seed, np.asarray(idx),
                          np.asarray(val), hash_bits=16)
    assert not np.array_equal(cheap, want)


def test_corpus_matches_the_source_shape():
    import corpus as corpus_mod
    _, cfg, _ = tiny(WORKLOADS[0])
    c = corpus_mod.corpus_of(cfg)
    idx, val = corpus_mod.Stream(c, 2**33 + 1, 0).batch(0, 2048)
    nnz = np.count_nonzero(np.asarray(val), axis=1)
    assert nnz.max() <= c.nnz_max and nnz.min() >= 1
    assert abs(nnz.mean() - c.nnz_mean) < 0.05 * c.nnz_mean
    idx, val = np.asarray(idx), np.asarray(val)
    for row, v in zip(idx[:64], val[:64]):  # distinct ids in every row
        ids = row[v != 0]
        assert len(np.unique(ids)) == len(ids)
    again = corpus_mod.Stream(c, 2**33 + 1, 0).batch(0, 2048)
    assert np.array_equal(np.asarray(again[0]), idx)


def test_brute_force_matches_a_direct_float64_scan():
    rng = np.random.default_rng(0)
    d = 256
    base = (rng.random((700, d)) < 0.1).astype(np.int8)
    q = (rng.random((5, d)) < 0.1).astype(np.uint8)
    bf = ref.BruteForce(q, "cham", d)
    import jax.numpy as jnp
    for s in range(0, 700, 300):
        bf.add_batch(jnp.asarray(base[s:s + 300]), np.arange(s, min(s + 300,
                                                                   700)))
    ids, dist, _, _ = bf.topk(7)
    wq = q.sum(1)[:, None]
    wb = base.sum(1)[None, :]
    inner = q.astype(np.int64) @ base.T.astype(np.int64)
    full, _ = ref.distances64(wq, wb, inner, "cham", d)
    want = np.lexsort((np.broadcast_to(np.arange(700), full.shape), full),
                      axis=1)[:, :7]
    assert np.array_equal(ids, want)
    assert np.allclose(dist, np.take_along_axis(full, want, axis=1))


def test_benchmark_json_names_a_reader_for_every_metric():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
