"""Multi-device behaviors (shard_map MoE, compressed psum, mini dry-run).

These need >1 XLA device, so each runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (per the assignment,
the main test process must keep seeing 1 device).
"""

import pytest as _pytest

pytestmark = _pytest.mark.slow  # LM-side e2e: excluded from the fast CI lane

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_moe_ep_equals_dense():
    """shard_map EP MoE == dense reference (same routing, ample capacity)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import ARCHS, reduce_for_smoke
        from repro.distributed.api import sharding_ctx
        from repro.nn import moe as M
        cfg = reduce_for_smoke(ARCHS['granite-moe-3b-a800m'])
        cfg = dataclasses.replace(cfg, n_experts=4, top_k=2)
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        p, _ = M.init_moe(jax.random.PRNGKey(0), cfg, tp=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
        dense = M.moe_block(p, cfg, x, impl='dense')
        with sharding_ctx(mesh):
            ep = M.moe_block(p, cfg, x, impl='ep', cf_send=4.0, cf_local=4.0)
        d, e = np.asarray(dense, np.float32), np.asarray(ep, np.float32)
        err = np.abs(d - e).max() / (np.abs(d).max() + 1e-9)
        assert err < 2e-2, err
        print('OK', err)
    """)
    assert "OK" in out


def test_compressed_psum_error_feedback():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def body(x, r):
            return compressed_psum(x, r, 'data')
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P('data'), P('data')), out_specs=(P('data'), P('data')),
                    check_vma=False))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 1024)).astype(np.float32))
        r = jnp.zeros_like(x)
        exact = np.asarray(x).mean(axis=0)
        # error feedback: averaged over steps, compressed mean -> exact mean
        acc = np.zeros(1024, np.float32)
        for i in range(8):
            y, r = f(x, r)
            acc += np.asarray(y[0])
        err = np.abs(acc / 8 - exact).max() / (np.abs(exact).max() + 1e-9)
        assert err < 0.05, err
        print('OK', err)
    """)
    assert "OK" in out


def test_mini_dryrun_8dev_mesh():
    """End-to-end dry-run machinery on a small mesh: lower+compile a reduced
    arch for train and decode, roofline terms finite."""
    out = _run("""
        import jax, dataclasses, numpy as np
        from repro.configs import ARCHS, SHAPES, reduce_for_smoke
        from repro.configs.base import ShapeConfig
        from repro.distributed.api import sharding_ctx, tree_shardings, DEFAULT_RULES
        from repro.launch import steps as S
        from repro.models import RuntimeConfig
        from repro.optim import AdamWConfig
        from repro.roofline import collective_bytes, cost_analysis_dict
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = reduce_for_smoke(ARCHS['qwen3-32b'])
        rt = RuntimeConfig(tp=4, scan_layers=False, attn_chunk=64, moe_impl='ep', loss_chunk=16)
        shape = ShapeConfig('mini_train', 64, 8, 'train')
        opt = AdamWConfig()
        rules = dict(DEFAULT_RULES); rules['embed_fsdp'] = None
        with sharding_ctx(mesh, rules):
            pshapes, paxes = S.abstract_params(cfg, rt)
            pshard = tree_shardings(pshapes, paxes, mesh)
            bspecs, baxes = S.batch_specs(cfg, shape)
            bshard = tree_shardings(bspecs, baxes, mesh)
            oshapes, oaxes = S.abstract_opt_state(pshapes, paxes, opt)
            oshard = tree_shardings(oshapes, oaxes, mesh)
            fn = S.make_train_step_fn(cfg, rt, opt)
            c = jax.jit(fn, in_shardings=(pshard, oshard, bshard),
                        donate_argnums=(0,1)).lower(pshapes, oshapes, bspecs).compile()
            ca = cost_analysis_dict(c)
            st = collective_bytes(c.as_text())
            assert ca['flops'] > 0
            assert st.total_bytes > 0, 'expected collectives on a 2x4 mesh'
            # decode as well
            dshape = ShapeConfig('mini_dec', 64, 8, 'decode')
            cshapes, caxes = S.abstract_caches(cfg, rt, 8, 64)
            cshard = tree_shardings(cshapes, caxes, mesh)
            dfn = S.make_decode_fn(cfg, rt)
            dc = jax.jit(dfn, in_shardings=(pshard, cshard, bshard if False else tree_shardings(*S.batch_specs(cfg, dshape), mesh)),
                         donate_argnums=(1,)).lower(pshapes, cshapes, S.batch_specs(cfg, dshape)[0]).compile()
            assert cost_analysis_dict(dc)['flops'] > 0
        print('OK')
    """)
    assert "OK" in out


def test_pipeline_parallelism_matches_sequential():
    """GPipe pipeline over a 4-stage axis == sequential stage stack."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline, reference_stack
        mesh = jax.make_mesh((4, 2), ('stage', 'data'))
        S, M, MB, D = 4, 6, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        w = jax.random.normal(ks[0], (S, D, D)) * (1.0 / np.sqrt(D))
        b = jax.random.normal(ks[1], (S, D)) * 0.1
        xs = jax.random.normal(ks[2], (M, MB, D))
        params = {'w': w, 'b': b}
        def block(p, x):
            return jnp.tanh(x @ p['w'] + p['b'])
        run = pipeline(block, mesh, n_stages=S, n_micro=M)
        got = run(params, xs)
        want = reference_stack(block, params, xs)
        err = float(jnp.abs(got - want).max())
        assert err < 1e-5, err
        print('OK', err)
    """)
    assert "OK" in out
