"""Multi-device tests (subprocess with XLA_FLAGS=8 fake devices):
sharded LGRASS phase-1 equivalence, elastic re-meshing, compressed psum,
and a reduced-mesh dry-run through the real launch machinery."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_phase1_equals_local():
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import random_connected_graph
        from repro.core.distributed import lgrass_phase1_distributed
        from repro.core.sparsify import phase1_device
        for seed in (0, 3):
            g = random_connected_graph(60, 140, seed=seed)
            mesh = jax.make_mesh((8,), ('data',))
            acc, dirty, d = lgrass_phase1_distributed(g, mesh, ('data',))
            u = jnp.asarray(g.u, jnp.int32); v = jnp.asarray(g.v, jnp.int32)
            w = jnp.asarray(g.w, jnp.float32)
            ds = jax.device_get(phase1_device(u, v, w, g.n, 32, True))
            ref = np.zeros(g.m, bool); ref[ds['perm']] = ds['accept_sorted']
            assert np.array_equal(acc, ref), seed
        print('OK')
    """)
    assert "OK" in out


def test_distributed_sparsify_equals_oracle():
    out = _run("""
        import jax, numpy as np
        from repro.core import random_connected_graph, baseline_sparsify
        from repro.core.distributed import lgrass_phase1_distributed
        from repro.core import _host as H
        from repro.core.recovery import recover
        g = random_connected_graph(50, 120, seed=5)
        b = baseline_sparsify(g, budget=10)
        mesh = jax.make_mesh((8,), ('data',))
        acc, dirty, d = lgrass_phase1_distributed(g, mesh, ('data',))
        tree = d['tree_mask'].astype(bool)
        crossing = d['crossing'].astype(bool)
        perm = d['perm'].astype(np.int64)
        group = np.full(g.m, -1, np.int64)
        group[perm] = d['gidx'].astype(np.int64)
        group[~crossing] = -1
        keys = np.where(~tree, d['crit'], np.float32(-np.inf))
        order = H.desc_stable_order_np(keys)[: int((~tree).sum())]
        final = recover(g.n, g.u.astype(np.int64), g.v.astype(np.int64),
                        tree, d['parent_t'], d['depth_t'], d['up'],
                        d['beta'], crossing, order, acc, group, dirty, 10)
        assert np.array_equal(tree | final, b.edge_mask)
        print('OK')
    """)
    assert "OK" in out


def test_elastic_remesh_and_compressed_psum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.ft.elastic import remesh_state
        from repro.optim.compression import compressed_psum

        # remesh 8 -> 4+idle devices (different topology)
        mesh8 = jax.make_mesh((8,), ('data',))
        mesh42 = jax.make_mesh((4, 2), ('data', 'model'))
        x = jax.device_put(np.arange(32, dtype=np.float32),
                           NamedSharding(mesh8, P('data')))
        state = {'w': x}
        spec = {'w': P('data')}
        out = remesh_state(state, spec, mesh42)
        assert np.array_equal(np.asarray(out['w']), np.arange(32))
        assert out['w'].sharding.mesh.shape['data'] == 4

        # compressed psum ~= exact psum
        mesh = jax.make_mesh((8,), ('d',))
        xs = np.random.default_rng(0).standard_normal((8, 64)).astype(
            np.float32)
        f = jax.jit(jax.shard_map(
            lambda a: compressed_psum(a[0], 'd')[None],
            mesh=mesh, in_specs=P('d'), out_specs=P('d')))
        got = np.asarray(f(xs))[0]
        want = xs.sum(0)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.05, err
        print('OK')
    """)
    assert "OK" in out


def test_reduced_mesh_dryrun_machinery():
    """Run the real dry-run flow (specs -> lower -> compile -> analyze) on
    an 8-device (2,2,2) pod/data/model mesh for two architectures."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        import repro.launch.mesh as M
        # shrink the production mesh for the 8-device CI environment
        M.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
            (2, 2, 2) if multi_pod else (4, 2),
            ('pod', 'data', 'model') if multi_pod else ('data', 'model'),
            axis_types=(AxisType.Auto,) * (3 if multi_pod else 2))
        from repro.launch import dryrun
        import repro.launch.dryrun as D
        rec1 = D.run_cell('mamba2-370m', 'train_4k', True, '/tmp/ci_dry',
                          force=True, micro_batches=2)
        assert rec1['hlo_flops_per_device'] > 0
        assert rec1['collective_bytes_per_device'] > 0
        rec2 = D.run_cell('granite-moe-3b-a800m', 'decode_32k', False,
                          '/tmp/ci_dry', force=True)
        assert rec2['memory']['temp_bytes'] > 0
        rec3 = D.run_lgrass_cell('case1_4k', True, '/tmp/ci_dry',
                                 force=True)
        assert rec3['hlo_bytes_per_device'] > 0
        print('OK')
    """, timeout=900)
    assert "OK" in out


def test_elastic_restart_on_smaller_mesh(tmp_path):
    """End-to-end elasticity: train on an 8-device mesh, checkpoint,
    restore + reshard onto a 4-device mesh, continue training — loss
    trajectory must continue from the checkpointed state."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.models.model import LM
        from repro.data.pipeline import DataConfig, TokenPipeline
        from repro.optim.optimizer import OptConfig
        from repro.train.train_step import make_train_state, make_train_step
        from repro.ckpt.checkpoint import Checkpointer
        from repro.ft.elastic import remesh_state, resolve_spec_for_mesh

        cfg = ARCHS['phi3-mini-3.8b'].reduced()
        model = LM(cfg)
        opt = OptConfig(peak_lr=5e-3, warmup_steps=2, total_steps=20)
        data = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=16, global_batch=8, seed=3))
        step = jax.jit(make_train_step(model, opt))
        ck = Checkpointer({str(tmp_path)!r}, async_save=False)

        # phase 1: 8-device data-parallel mesh
        mesh8 = jax.make_mesh((8,), ('data',))
        state = make_train_state(model, jax.random.PRNGKey(0))
        state = jax.device_put(state, NamedSharding(mesh8, P()))
        losses = []
        for i in range(6):
            batch = jax.device_put(data.batch(i),
                                   NamedSharding(mesh8, P('data')))
            state, m = step(state, batch)
            losses.append(float(m['loss']))
        ck.save(6, state)

        # phase 2: 'failure' -> resume on a 4-device mesh
        mesh4 = jax.make_mesh((4, 2), ('data', 'model'))
        template = jax.tree.map(np.asarray, jax.device_get(state))
        restored = ck.restore(6, template)
        spec_tree = jax.tree.map(lambda _: P(), restored)
        state2 = remesh_state(restored, spec_tree, mesh4)
        for i in range(6, 12):
            batch = jax.device_put(data.batch(i),
                                   NamedSharding(mesh4, P('data')))
            state2, m = step(state2, batch)
            losses.append(float(m['loss']))
        assert int(state2['opt']['step']) == 12
        assert all(np.isfinite(losses))
        print('OK', round(losses[0], 3), round(losses[-1], 3))
    """)
    assert "OK" in out
