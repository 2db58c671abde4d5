"""Distribution-layer tests that need multiple devices: run in a SUBPROCESS
with forced host devices so the main pytest process keeps 1 device (the
dry-run contract).  Covers: sharding rules, mesh-lowered train step,
elastic checkpoint resharding, cross-pod sign compression, pipeline
parallelism, and a miniature dry-run."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_param_specs_follow_rules():
    out = run_with_devices("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import reduced_for_smoke
        from repro.configs.registry import get_config
        from repro.distributed import sharding as shd
        from repro.launch.specs import abstract_params

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = reduced_for_smoke(get_config("llama3_8b"))
        with jax.sharding.set_mesh(mesh):
            params = abstract_params(cfg)
            specs = shd.param_specs(params)
        # embed table (512, 64): vocab over model, d over data
        assert specs["embed"]["table"] == P("model", "data"), specs["embed"]
        # layer params carry a leading stacked-scan dim (always None)
        l0 = specs["stages"][0]["l0"]
        assert l0["attn"]["wq"] == P(None, "data", "model")
        assert l0["attn"]["wo"] == P(None, "model", "data")
        assert l0["mlp"]["w_down"] == P(None, "model", "data")
        assert l0["norm1"]["scale"] == P(None, None)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_runs_on_mesh():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import (ParallelConfig, TrainConfig,
                                        reduced_for_smoke)
        from repro.configs.registry import get_config
        from repro.distributed import sharding as shd
        from repro.models import transformer as T
        from repro.train import optimizer as opt
        from repro.train.train_step import make_train_step

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = reduced_for_smoke(get_config("internlm2_1_8b"))
        pcfg = ParallelConfig(remat="block", sequence_parallel=True)
        tcfg = TrainConfig(z_loss=0.0)
        with jax.sharding.set_mesh(mesh):
            params = T.init_params(cfg, jax.random.PRNGKey(0))
            psh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), shd.param_specs(params),
                is_leaf=lambda x: isinstance(x, P))
            params = jax.tree_util.tree_map(jax.device_put, params, psh)
            state = opt.init_state(params)
            rng = np.random.default_rng(0)
            batch = {
                "tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (4, 32))),
                "labels": jnp.asarray(rng.integers(3, cfg.vocab_size, (4, 32))),
            }
            step = jax.jit(make_train_step(cfg, pcfg, tcfg))
            p2, s2, metrics = step(params, state, batch)
            loss1 = float(metrics["loss"])
            # single-device reference: same math, no mesh
        print("LOSS", loss1)
        assert np.isfinite(loss1)
        print("OK")
    """)
    assert "OK" in out


def test_train_step_mesh_matches_single_device():
    """Distribution must not change the math: loss on a 2x4 mesh equals the
    unsharded single-device loss for identical params/batch."""
    code_tpl = """
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs.base import (ParallelConfig, TrainConfig,
                                        reduced_for_smoke)
        from repro.configs.registry import get_config
        from repro.models import transformer as T
        from repro.train.train_step import loss_fn

        cfg = reduced_for_smoke(get_config("qwen2_7b"))
        pcfg = ParallelConfig(remat="none", sequence_parallel={SP})
        tcfg = TrainConfig(z_loss=0.0)
        params = T.init_params(cfg, jax.random.PRNGKey(7))
        rng = np.random.default_rng(3)
        batch = {{
            "tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (4, 32))),
            "labels": jnp.asarray(rng.integers(3, cfg.vocab_size, (4, 32))),
        }}
        {MESH}
        print("LOSS=%.6f" % float(loss))
    """
    single = run_with_devices(code_tpl.format(SP="False", MESH="""
        loss, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b, pcfg, tcfg))(params, batch)
    """), n_devices=1)
    meshed = run_with_devices(code_tpl.format(SP="True", MESH="""
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        with jax.sharding.set_mesh(mesh):
            loss, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b, pcfg, tcfg))(params, batch)
    """), n_devices=8)
    l1 = float(single.split("LOSS=")[1].strip().split()[0])
    l2 = float(meshed.split("LOSS=")[1].strip().split()[0])
    assert abs(l1 - l2) < 5e-3, (l1, l2)


def test_elastic_checkpoint_reshard():
    out = run_with_devices("""
        import os, tempfile
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer

        tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
                "b": np.ones(8, np.float32)}
        d = tempfile.mkdtemp()
        # save under mesh A (8 devices, 8-way model)
        mesh_a = jax.make_mesh((8,), ("model",))
        sh_a = {"w": NamedSharding(mesh_a, P("model", None)),
                "b": NamedSharding(mesh_a, P("model"))}
        tree_a = jax.tree_util.tree_map(jax.device_put, tree, sh_a)
        ck = Checkpointer(d, async_save=False)
        ck.save(1, tree_a)
        # restore under mesh B (2x4): the elastic/degraded path
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        sh_b = {"w": NamedSharding(mesh_b, P("model", "data")),
                "b": NamedSharding(mesh_b, P("model"))}
        got, step = ck.restore(tree, shardings=sh_b)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
        assert got["w"].sharding == sh_b["w"]
        print("OK")
    """)
    assert "OK" in out


def test_cross_pod_sign_compression_semantics():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.train.grad_compress import cross_pod_sign_allreduce

        mesh = jax.make_mesh((2, 2), ("pod", "data"))
        rng = np.random.default_rng(0)
        # per-pod gradients: pod 0 and pod 1 disagree on some signs
        g0 = rng.standard_normal(256).astype(np.float32)
        g1 = g0.copy(); g1[:64] = -g1[:64]
        stacked = jnp.asarray(np.stack([g0, g0, g1, g1]))  # (pod*data, n)

        def f(g):
            return cross_pod_sign_allreduce(g[0], "pod")[None]

        out = jax.shard_map(
            f, mesh=mesh, in_specs=P(("pod", "data")),
            out_specs=P(("pod", "data")), check_vma=False)(stacked)
        out = np.asarray(out)
        # ties (majority 1 vs 1) resolve to +; where both pods agree the sign
        # must match; magnitude = pod-mean of mean|g|
        agree = np.sign(g0[64:])
        np.testing.assert_array_equal(np.sign(out[0][64:]), agree)
        scale = (np.abs(g0).mean() + np.abs(g1).mean()) / 2
        assert np.allclose(np.abs(out[0]), scale, rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_apply, bubble_fraction

        mesh = jax.make_mesh((4,), ("stage",))
        S, M, mb, dim = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((S, dim, dim)).astype(np.float32) * 0.3)
        xs = jnp.asarray(rng.standard_normal((M, mb, dim)).astype(np.float32))

        def stage_fn(params, x):
            return jnp.tanh(x @ params)

        got = pipeline_apply(mesh, stage_fn, w, xs, axis="stage")
        want = xs
        for s in range(S):
            want = jnp.tanh(want @ w[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_mini_dryrun_both_meshes():
    """Miniature end-to-end dry-run: 16 forced devices, (2,2,4) multi-pod
    mesh over a reduced arch — validates the dryrun driver logic without the
    512-device production run (which runs via python -m repro.launch.dryrun)."""
    out = run_with_devices("""
        import jax, dataclasses
        import jax.numpy as jnp
        from repro.configs.base import SHAPES, ParallelConfig, reduced_for_smoke
        from repro.configs.registry import get_config
        from repro.launch.dryrun import lower_cell
        from repro.launch import roofline as rl

        mesh = jax.make_mesh((2, 2, 4), ("pod", "data", "model"))
        cfg = reduced_for_smoke(get_config("llama3_8b"))
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=128,
                                    global_batch=8)
        pcfg = ParallelConfig(remat="block", sequence_parallel=True)
        lowered = lower_cell(cfg, shape, mesh, pcfg)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list): cost = cost[0]
        assert cost.get("flops", 0) > 0
        coll = rl.parse_collectives(compiled.as_text(), default_group=16)
        assert coll.count > 0  # sharded program must communicate
        shape_d = dataclasses.replace(SHAPES["decode_32k"], seq_len=256,
                                      global_batch=8)
        lowered = lower_cell(cfg, shape_d, mesh, pcfg)
        compiled = lowered.compile()
        print("OK")
    """, n_devices=16, timeout=900)
    assert "OK" in out


def test_index_sharded_engine_matches_unsharded():
    """QueryEngine.shard places the store rows across a data mesh; results
    (ids and float bits) must match the unsharded engine exactly, including
    for rows added AFTER sharding."""
    out = run_with_devices("""
        import numpy as np
        import jax
        from repro.core import CabinParams
        from repro.index import QueryEngine

        n, d = 400, 256
        rng = np.random.default_rng(0)
        x = np.zeros((48, n), np.int32)
        for i in range(48):
            density = int(rng.integers(10, 60))
            idx = rng.choice(n, size=density, replace=False)
            x[i, idx] = rng.integers(1, 8, size=density)
        params = CabinParams.create(n, d, seed=2)

        plain = QueryEngine(params)
        plain.add_dense(x)

        mesh = jax.make_mesh((4,), ("data",))
        sharded = QueryEngine(params)
        sharded.add_dense(x[:24])
        sharded.shard(mesh)
        sharded.add_dense(x[24:])
        assert len(jax.devices()) == 4

        pi, pv = plain.topk(x[:6], 5)
        si, sv = sharded.topk(x[:6], 5)
        np.testing.assert_array_equal(pi, si)
        np.testing.assert_array_equal(pv, sv)
        pr = plain.radius(x[:6], 30.0)
        sr = sharded.radius(x[:6], 30.0)
        for a, b in zip(pr, sr):
            np.testing.assert_array_equal(a, b)
        sharded.remove(np.arange(5, 15))
        sharded.compact()
        plain.remove(np.arange(5, 15))
        plain.compact()
        np.testing.assert_array_equal(plain.topk(x[:6], 5)[1],
                                      sharded.topk(x[:6], 5)[1])
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def test_index_sharded_eight_devices_any_history_bit_identical():
    """The acceptance run: 8 real (virtual CPU) devices, one partition
    group per device, interleaved add/remove/compact plus a spec migration
    — topk, radius AND pairwise bit-identical to the unsharded engine at
    every step, both metrics, including queries served mid-migration."""
    out = run_with_devices("""
        import numpy as np
        import jax
        from repro.core import CabinParams
        from repro.index import QueryEngine

        assert len(jax.devices()) == 8
        n, d = 400, 256
        rng = np.random.default_rng(1)
        x = np.zeros((80, n), np.int32)
        for i in range(80):
            density = int(rng.integers(10, 60))
            idx = rng.choice(n, size=density, replace=False)
            x[i, idx] = rng.integers(1, 8, size=density)
        params = CabinParams.create(n, d, seed=2)
        mesh = jax.make_mesh((8,), ("data",))

        for metric in ("cham", "hamming"):
            r = 60.0 if metric == "cham" else 30.0
            kw = dict(metric=metric, band_rows=8, merge_ratio=0.5,
                      cache_entries=0)
            plain = QueryEngine(params, **kw)
            sharded = QueryEngine(params, **kw)
            sharded.shard(mesh)

            def parity(q):
                pi, pv = plain.topk(q, 5)
                si, sv = sharded.topk(q, 5)
                np.testing.assert_array_equal(pi, si)
                np.testing.assert_array_equal(pv, sv)
                for a, b in zip(plain.radius(q, r), sharded.radius(q, r)):
                    np.testing.assert_array_equal(a, b)
                if not plain.migrating:
                    pp = plain.pairwise(q[:2])
                    sp = sharded.pairwise(q[:2])
                    np.testing.assert_array_equal(pp[0], sp[0])
                    np.testing.assert_array_equal(pp[1], sp[1])

            for eng in (plain, sharded):
                eng.add_dense(x[:40])
            parity(x[:6])
            for eng in (plain, sharded):
                eng.remove(np.arange(3, 21, 2))
            parity(x[:6])
            for eng in (plain, sharded):
                eng.compact()
                eng.add_dense(x[40:64])
            parity(x[:6])
            for eng in (plain, sharded):
                eng.migrate(d=320, drive="manual", batch_rows=16)
                eng.migration_step()
            parity(x[:6])               # mid-migration, across spec tiers
            for eng in (plain, sharded):
                eng.add_dense(x[64:])   # acked ingest lands in fresh tier
                eng.migrate_all()
            parity(x[:6])
            assert sharded.stats()["n_shards"] == 8
        print("OK")
    """, n_devices=8)
    assert "OK" in out
