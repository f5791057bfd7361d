"""Pipeline-parallel (pod-axis) tests: stage split/merge roundtrip, and
numerical equivalence pipeline(S stages) == sequential, incl. gradients —
run with 4 forced host devices in a subprocess (device count is process-global)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.pipeline import merge_stages, split_stages

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stage_split_roundtrip():
    t = {"w": jnp.arange(24.0).reshape(6, 4)}
    s = split_stages(t, 3)
    assert s["w"].shape == (3, 2, 4)
    np.testing.assert_array_equal(np.asarray(merge_stages(s)["w"]),
                                  np.asarray(t["w"]))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import auto_mesh
    from repro.launch.pipeline import make_pipelined_apply, split_stages

    L, D, M, B = 4, 8, 3, 2
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(0, 0.1, (L, D)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(M, B, D)), jnp.float32)

    def layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def stage_fn(sp, h):
        def body(h, lp):
            return layer(lp, h), None
        h, _ = jax.lax.scan(body, h, sp)
        return h

    # sequential reference (all L layers)
    def seq(h):
        def body(h, lp):
            return layer(lp, h), None
        h, _ = jax.lax.scan(body, h, stacked)
        return h
    want = jax.vmap(seq)(x)

    for n_stages in (2, 4):
        mesh = auto_mesh((n_stages,), ("pod",), jax.devices()[:n_stages])
        staged = split_stages(stacked, n_stages)
        apply_fn = make_pipelined_apply(stage_fn, n_stages, mesh)
        with mesh:
            got = apply_fn(staged, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

        # gradients flow through the pipeline (reverse ppermute)
        def loss(sp):
            with mesh:
                return jnp.sum(apply_fn(sp, x) ** 2)
        g = jax.grad(loss)(staged)

        def loss_seq(st):
            return jnp.sum(jax.vmap(seq)(x) ** 2) if False else None
        def loss_ref(stk):
            def seq2(h):
                def body(h, lp):
                    return layer(lp, h), None
                h, _ = jax.lax.scan(body, h, stk)
                return h
            return jnp.sum(jax.vmap(seq2)(x) ** 2)
        g_ref = jax.grad(loss_ref)(stacked)
        g_merged = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), g)
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(g_merged[k]),
                                       np.asarray(g_ref[k]),
                                       rtol=1e-4, atol=1e-4)
    print("PIPELINE_OK")
""")


def test_pipeline_matches_sequential_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PIPELINE_OK" in res.stdout
