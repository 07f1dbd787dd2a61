"""Multi-device integration tests (8 host CPU devices via subprocess —
the dry-run rule: tests themselves must not set the device-count flag
globally)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_kahan_all_reduce_two_pods():
    """n=2 (pod axis): compensated all-reduce is exact-to-bound and costs
    the same payload as psum."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np, math
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed import collectives

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        # adversarial: large cancellation between the two pods
        a = (rng.standard_normal(4096) * 1e6).astype(np.float32)
        b = (-a + rng.standard_normal(4096) * 1e-2).astype(np.float32)
        x = np.stack([a, b])                        # [2, n]
        exact = np.float64(a) + np.float64(b)

        def f(v):
            out = collectives.kahan_all_reduce(v[0], "pod")
            return out[None]
        g = shard_map(f, mesh=mesh, in_specs=(P("pod", None),),
                      out_specs=P("pod", None))
        got = np.asarray(jax.jit(g)(jnp.asarray(x)))[0]

        def fp(v):
            return jax.lax.psum(v[0], "pod")[None]
        gp = shard_map(fp, mesh=mesh, in_specs=(P("pod", None),),
                       out_specs=P("pod", None))
        psum_res = np.asarray(jax.jit(gp)(jnp.asarray(x)))[0]

        err_k = np.abs(got - exact).max()
        err_p = np.abs(psum_res - exact).max()
        assert err_k <= err_p + 1e-9, (err_k, err_p)
        eps = np.finfo(np.float32).eps
        bound = 8 * eps * np.abs(np.float64(a)).max()
        assert err_k <= bound, (err_k, bound)
        print("OK", err_k, err_p)
    """)


def test_kahan_ring_all_reduce_eight():
    """n=8 ring reduce-scatter+all-gather with (s,c) payload: compensated
    error bound independent of n; matches fsum to a few eps."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np, math
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed import collectives

        n = 8
        mesh = jax.make_mesh((n,), ("pod",))
        rng = np.random.default_rng(1)
        x = (rng.standard_normal((n, 1000))
             * 10.0 ** rng.integers(-4, 5, (n, 1000))).astype(np.float32)
        exact = np.sum(np.float64(x), axis=0)

        def f(v):
            return collectives.kahan_all_reduce(v[0], "pod")[None]
        g = shard_map(f, mesh=mesh, in_specs=(P("pod", None),),
                      out_specs=P("pod", None))
        got = np.asarray(jax.jit(g)(jnp.asarray(x)))[0]
        err = np.abs(got - exact)
        eps = np.finfo(np.float32).eps
        bound = 16 * eps * np.sum(np.abs(np.float64(x)), axis=0) + 1e-20
        assert (err <= bound).all(), float(err.max())

        def fnaive(v):
            return collectives.naive_ring_all_reduce(v[0], "pod")[None]
        gn = shard_map(fnaive, mesh=mesh, in_specs=(P("pod", None),),
                       out_specs=P("pod", None))
        naive = np.asarray(jax.jit(gn)(jnp.asarray(x)))[0]
        assert err.mean() <= np.abs(naive - exact).mean() + 1e-9
        print("OK")
    """)


def test_ef_quantized_all_reduce():
    """EF int8 all-reduce: per-step quantization error bounded; residual
    repays it so the T-step accumulated sum converges to the true one."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression

        n = 4
        mesh = jax.make_mesh((n, 2), ("pod", "x"))
        rng = np.random.default_rng(2)
        g = rng.standard_normal((n, 512)).astype(np.float32)
        true_sum = g.sum(axis=0)

        def f(v, r):
            out, st = compression.ef_quantized_all_reduce(
                v[0], compression.EFState(r[0]), "pod")
            return out[None], st.residual[None]
        fn = shard_map(f, mesh=mesh,
                       in_specs=(P("pod", None), P("pod", None)),
                       out_specs=(P("pod", None), P("pod", None)))
        fn = jax.jit(fn)

        resid = jnp.zeros_like(jnp.asarray(g))
        acc = np.zeros_like(true_sum)
        T = 30
        for _ in range(T):
            out, resid = fn(jnp.asarray(g), resid)
            acc += np.asarray(out)[0]
        # accumulated mean converges to the true sum (error feedback works)
        err = np.abs(acc / T - true_sum).max()
        scale = np.abs(g).max()
        assert err < 0.02 * scale, (err, scale)
        print("OK", err)
    """)


def test_pipeline_matches_sequential():
    run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import make_pipeline_fn

        S, M, mb, d = 4, 6, 2, 16
        mesh = jax.make_mesh((S, 2), ("stage", "other"))
        rng = np.random.default_rng(3)
        ws = jnp.asarray(rng.standard_normal((S, d, d)).astype(np.float32) * 0.3)
        x = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        pipe = make_pipeline_fn(stage_fn, mesh, "stage")
        got = jax.jit(lambda w, v: pipe({"w": w}, v))(ws, x)

        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        # pipeline-parallel backward exists and is finite
        def loss(w):
            return jnp.sum(pipe({"w": w}, x) ** 2)
        gr = jax.jit(jax.grad(loss))(ws)
        assert np.isfinite(np.asarray(gr)).all()
        assert float(jnp.abs(gr).sum()) > 0
        print("OK")
    """)


def test_mini_dryrun_on_test_mesh():
    """The dry-run machinery end-to-end on an 8-device (2,2,2) mesh with a
    reduced config: lower + compile + roofline extraction all function."""
    run_script("""
        import jax, math
        from repro.configs import get_config, reduced
        from repro.data import synthetic
        from repro.distributed import sharding
        from repro.launch.mesh import make_test_mesh
        from repro.models import api, common
        from repro.optim import adamw
        from repro.train import steps
        from repro.ecm import hlo_cost

        cfg = reduced(get_config("olmoe-1b-7b"))
        mesh = make_test_mesh(multi_pod=True)
        sch = api.schema(cfg)
        pshard = sharding.param_shardings(sch, mesh)
        params = common.abstract_params(sch)
        opt_cfg = adamw.AdamWConfig(kahan=True)
        opt = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), params)
        oshard = adamw.AdamWState(
            count=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            m=pshard, v=pshard, carry=pshard)
        batch = synthetic.train_batch_struct(cfg, 64, 8)
        bshard = sharding.batch_shardings(batch, mesh, 8)
        fn = steps.build_train_step(cfg, opt_cfg)
        jitted = jax.jit(fn, in_shardings=(pshard, oshard, bshard, None),
                         donate_argnums=(0, 1))
        with mesh, sharding.activation_sharding(mesh):
            lowered = jitted.lower(params, opt,
                                   batch, jax.ShapeDtypeStruct((), jax.numpy.int32))
        compiled = lowered.compile()
        cost = hlo_cost.analyze(compiled.as_text())
        assert cost.flops > 0 and cost.bytes_accessed > 0
        print("OK", cost.flops)
    """)


def test_elastic_checkpoint_remesh():
    """Save under a (2,2,2) sharded mesh, restore under (4,2) and (1,1)."""
    run_script("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager

        mesh_a = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        mesh_b = jax.make_mesh((4, 2), ("data", "model"))
        tree = {
            "w": jax.device_put(
                np.arange(64, dtype=np.float32).reshape(8, 8),
                NamedSharding(mesh_a, P("data", "model"))),
            "b": jax.device_put(np.ones(8, np.float32),
                                NamedSharding(mesh_a, P("model"))),
            "step": jnp.asarray(7),
        }
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(7, tree)
            assert mgr.latest_step() == 7
            shard_b = {
                "w": NamedSharding(mesh_b, P("data", "model")),
                "b": NamedSharding(mesh_b, P("model")),
                "step": NamedSharding(mesh_b, P()),
            }
            restored = mgr.restore(7, tree, shard_b)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(tree["w"]))
            assert restored["w"].sharding.mesh.shape == {"data": 4, "model": 2}
            # and fully replicated single-device restore
            restored1 = mgr.restore(7, tree)
            np.testing.assert_array_equal(np.asarray(restored1["b"]),
                                          np.ones(8, np.float32))
        print("OK")
    """)


@pytest.mark.parametrize("kv_heads,split", [(8, True), (2, False)])
def test_serve_cache_shardings_split_whole_kv_heads(kv_heads, split):
    """GQA pools are stored [L, NB, bs, hkv * d]: the model axis splits
    their feature dim only where the kv heads divide by it, so a rank
    holds whole heads, with the scale tiles split alike; the block axis
    goes over data. Shapes only, on an abstract (data 2, model 4) mesh."""
    from jax.sharding import AbstractMesh

    from repro.configs import get_config, reduced
    from repro.distributed.sharding import serve_cache_shardings
    from repro.models import api
    from repro.models.paged import PagedLayout

    cfg = reduced(get_config("qwen1.5-0.5b")).with_(
        num_layers=2, num_heads=8, num_kv_heads=kv_heads, head_dim=64,
        kv_dtype="int8")
    mesh = AbstractMesh((2, 4), ("data", "model"))
    struct = api.cache_specs(cfg, 4, PagedLayout(16, 4), num_blocks=16)
    shardings = serve_cache_shardings(cfg, struct, mesh, 4)
    specs = {k: v.spec for k, v in shardings[0].items()}
    feature = "model" if split else None
    for name in ("kpool", "vpool", "kscale", "vscale"):
        assert tuple(specs[name]) == (None, "data", None, feature), name
