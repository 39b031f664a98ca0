"""Distributed behaviour on 8 fake host devices (subprocess-isolated so the
main pytest process keeps a single device — dryrun.py is the only place
allowed to see 512).

Covers: sharded end-to-end train step on the debug mesh, the explicit
pod-wise compressed all-reduce (shard_map), resharding checkpoint restore,
and the loop-aware HLO cost parser against a hand-countable program.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_end_to_end():
    out = run_sub("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp, functools
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeConfig
        from repro.data.pipeline import make_pipeline
        from repro.distributed import sharding as SH
        from repro.launch.mesh import make_debug_mesh
        from repro.runtime import steps

        cfg, run = get_config('minitron-8b', smoke=True)
        run = dataclasses.replace(run, grad_accum=1)
        mesh = make_debug_mesh(2, 4)
        shape = ShapeConfig('s', 'train', 32, 8)
        batch = {k: jnp.asarray(v) for k, v in
                 make_pipeline(cfg).batch_at(0, shape).items()}
        with jax.set_mesh(mesh):
            state = steps.init_train_state(jax.random.PRNGKey(0), cfg, run)
            sspec = jax.eval_shape(lambda: state)
            shd = SH.make_param_shardings(mesh, sspec.params, cfg, run)
            state = state._replace(
                params=jax.device_put(state.params, shd))
            fn = jax.jit(functools.partial(steps.train_step, cfg=cfg,
                                           run=run))
            s2, m = fn(state, batch)
            l1 = float(m['loss'])
            s3, m2 = fn(s2, batch)
            print('LOSSES', l1, float(m2['loss']))
        assert np.isfinite(l1)
    """)
    l1, l2 = [float(x) for x in out.split("LOSSES")[1].split()]
    assert l2 < l1  # same batch twice -> loss must drop


def test_podwise_compressed_step_reduces_and_runs():
    out = run_sub("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeConfig
        from repro.data.pipeline import make_pipeline
        from repro.distributed import sharding as SH
        from repro.launch.mesh import make_debug_mesh
        from repro.runtime import steps

        cfg, run = get_config('minitron-8b', smoke=True)
        run = dataclasses.replace(run, grad_accum=1,
                                  grad_compression='dwt:1')
        mesh = make_debug_mesh(2, 2, multi_pod=True)
        shape = ShapeConfig('s', 'train', 32, 8)
        batch = {k: jnp.asarray(v) for k, v in
                 make_pipeline(cfg).batch_at(0, shape).items()}
        with jax.set_mesh(mesh):
            state = steps.init_train_state(jax.random.PRNGKey(0), cfg, run)
            step = steps.make_train_step_podwise(mesh, cfg, run)
            jstep = jax.jit(step)
            s2, m = jstep(state, batch)
            s3, m2 = jstep(s2, batch)
            print('LOSSES', float(m['loss']), float(m2['loss']))
            # the pod all-reduce must run on the COMPRESSED rep: check the
            # HLO for a DCN-sized all-reduce strictly smaller than params
            txt = jax.jit(step).lower(state, batch).compile().as_text()
            import re
            ars = re.findall(r'all-reduce', txt)
            print('NUM_AR', len(ars))
    """)
    l1, l2 = [float(x) for x in out.split("LOSSES")[1].split()[:2]]
    assert l2 < l1
    assert int(out.split("NUM_AR")[1].split()[0]) > 0


def test_resharding_restore():
    """Checkpoint saved unsharded restores onto a 2x4 mesh (elastic)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.launch.mesh import make_debug_mesh

        tree = {'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        d = tempfile.mkdtemp()
        Checkpointer(d).save(3, tree)

        mesh = make_debug_mesh(2, 4)
        sh = {'w': NamedSharding(mesh, P('data', 'model'))}
        restored, step = Checkpointer(d).restore(
            {'w': jnp.zeros((8, 8))}, shardings=sh)
        assert step == 3
        assert restored['w'].sharding.is_equivalent_to(sh['w'], 2)
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(tree['w']))
        print('OK')
    """)


def test_hlo_cost_parser_exact_on_known_program():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_analysis as HA
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        L, B, D = 7, 64, 256
        def f(x, ws):
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, ws)
            return (h.astype(jnp.float32) ** 2).sum()
        x = jax.ShapeDtypeStruct((B, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        c = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P('data', None)),
            NamedSharding(mesh, P(None, None, 'model')))).lower(x, ws)\
            .compile()
        cost = HA.parse_costs(c.as_text())
        expect = L * 2 * B * D * D / 8
        print('RATIO', cost.flops / expect)
    """)
    ratio = float(out.split("RATIO")[1].split()[0])
    assert 0.95 < ratio < 1.1


def test_collective_parser_on_known_program():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_analysis as HA
        mesh = jax.make_mesh((8,), ('model',))
        def f(x, w):
            return jax.nn.relu(x @ w).sum()
        x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
        w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        c = jax.jit(jax.grad(f), in_shardings=(
            NamedSharding(mesh, P(None, 'model')),
            NamedSharding(mesh, P('model', None)))).lower(x, w).compile()
        st = HA.parse_collectives(c.as_text())
        print('WIRE', st.total_wire_bytes, sum(st.counts.values()))
    """)
    wire, n = out.split("WIRE")[1].split()[:2]
    assert float(wire) > 0 and int(n) > 0
