"""Multi-device CPU tests (8 host devices via subprocess isolation — the
main pytest process must keep seeing exactly 1 device)."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(body: str, n: int = 8) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_8dev():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import get_smoke_config, RunConfig
        from repro.launch.steps import make_train_step, default_hyper
        from repro.launch.mesh import make_test_mesh
        from repro.models import build
        from repro.sharding import abstract_tree, shard_batch_specs, tree_shardings
        from repro.train.optimizer import state_specs, init_state
        from repro.models import batch_specs
        from repro.configs.base import ShapeConfig

        cfg = get_smoke_config('llama3.2-1b')
        run = RunConfig(attn_impl='xla')
        mesh = make_test_mesh()
        bundle = build(cfg)
        hyper = default_hyper(cfg, run)
        with mesh:
            params = bundle.init(jax.random.key(0))
            pshard = tree_shardings(bundle.spec, mesh)
            params = jax.device_put(params, pshard)
            opt = init_state(params, hyper)
            oshard = tree_shardings(state_specs(bundle.spec, hyper), mesh)
            opt = jax.device_put(opt, oshard)
            state = {'params': params, 'opt': opt}
            rng = np.random.default_rng(0)
            batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
                     'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
            step = jax.jit(make_train_step(cfg, run, hyper), donate_argnums=(0,))
            state, m = step(state, batch)
            l1 = float(m['loss'])
            state, m = step(state, batch)
            l2 = float(m['loss'])
        assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1, (l1, l2)
        # compare with single-logical-device result
        print('SHARDED_OK', l1)
    """)
    assert "SHARDED_OK" in out


def test_sharded_matches_unsharded_loss():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import get_smoke_config
        from repro.launch.mesh import make_test_mesh
        from repro.models import build
        from repro.sharding import tree_shardings

        cfg = get_smoke_config('qwen2-0.5b')
        bundle = build(cfg)
        params = bundle.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
                 'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
        l_un, _ = jax.jit(lambda p, b: bundle.loss(p, b))(params, batch)
        mesh = make_test_mesh()
        with mesh:
            ps = jax.device_put(params, tree_shardings(bundle.spec, mesh))
            l_sh, _ = jax.jit(lambda p, b: bundle.loss(p, b))(ps, batch)
        d = abs(float(l_un) - float(l_sh))
        assert d < 1e-2, d
        print('PARITY_OK', d)
    """)
    assert "PARITY_OK" in out


def test_grad_compress_cross_pod_psum():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.train import grad_compress

        devs = np.asarray(jax.devices()).reshape(2, 4)
        mesh = Mesh(devs, ('pod', 'data'))
        g = jax.random.normal(jax.random.key(0), (2, 64))  # per-pod grads

        def body(g_local, e_local):
            deq, e = grad_compress.compress_grads({'w': g_local}, {'w': e_local})
            out = grad_compress.podwise_mean(deq, 'pod')
            return out['w'], e['w']

        f = jax.shard_map(body, mesh=mesh, in_specs=(P('pod'), P('pod')),
                      out_specs=(P('pod'), P('pod')))
        e0 = jnp.zeros((2, 64))
        out, e = f(g, e0)
        want = jnp.mean(g, axis=0)
        err = float(jnp.max(jnp.abs(out[0] - want)))
        scale = float(jnp.max(jnp.abs(g))) / 127
        assert err < 2.1 * scale, (err, scale)   # int8 quantization bound
        print('PSUM_OK', err)
    """)
    assert "PSUM_OK" in out


def test_dryrun_cell_small_mesh():
    """End-to-end dry-run machinery on an 8-device mesh with a smoke config
    (the 512-device production run is exercised by launch/dryrun.py)."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.configs.base import get_smoke_config, RunConfig, ShapeConfig
        from repro.launch.steps import make_train_step, default_hyper
        from repro.launch.mesh import make_test_mesh
        from repro.models import build, batch_specs
        from repro.sharding import abstract_tree, shard_batch_specs
        from repro.train.optimizer import state_specs
        from repro.launch import roofline as rl

        cfg = get_smoke_config('jamba-v0.1-52b')
        shape = ShapeConfig('t', 64, 8, 'train')
        run = RunConfig(attn_impl='xla')
        mesh = make_test_mesh()
        bundle = build(cfg)
        hyper = default_hyper(cfg, run)
        with mesh:
            state = {'params': abstract_tree(bundle.spec, mesh),
                     'opt': abstract_tree(state_specs(bundle.spec, hyper), mesh)}
            batch = shard_batch_specs(batch_specs(cfg, shape), mesh)
            step = make_train_step(cfg, run, hyper)
            lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            coll = rl.collective_bytes(compiled.as_text())
        if isinstance(cost, (list, tuple)):   # pre-0.5 jax returns [dict]
            cost = cost[0] if cost else {}
        assert cost.get('flops', 0) > 0
        print('DRYRUN_OK', int(cost['flops']), coll['n_ops'])
    """)
    assert "DRYRUN_OK" in out


def test_elastic_restore_across_mesh_sizes():
    out = run_with_devices("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs.base import get_smoke_config
        from repro.ft.checkpoint import CheckpointManager
        from repro.ft.elastic import choose_mesh_shape, restore_elastic
        from repro.models import build
        from repro.sharding import tree_shardings

        cfg = get_smoke_config('olmo-1b')
        bundle = build(cfg)
        params = bundle.init(jax.random.key(0))
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, async_save=False)
            # save while sharded on an 8-device mesh
            devs = np.asarray(jax.devices()).reshape(2, 4)
            mesh8 = Mesh(devs, ('data', 'model'))
            p8 = jax.device_put(params, tree_shardings(bundle.spec, mesh8))
            cm.save(1, p8)
            # restore onto a 4-device mesh (elastic shrink)
            assert choose_mesh_shape(4, prefer_model=4) == (1, 4)
            devs4 = np.asarray(jax.devices()[:4]).reshape(1, 4)
            mesh4 = Mesh(devs4, ('data', 'model'))
            p4 = restore_elastic(cm, 1, params, bundle.spec, mesh4)
            same = jax.tree_util.tree_map(
                lambda a, b: np.allclose(np.asarray(a), np.asarray(b)),
                params, p4)
            assert all(jax.tree_util.tree_leaves(same))
        print('ELASTIC_OK')
    """)
    assert "ELASTIC_OK" in out
