"""Multi-device tests for hand-scheduled collectives and sharding rules.

These need >1 device, so they run in a subprocess that sets
XLA_FLAGS=--xla_force_host_platform_device_count before importing jax
(the main test process must keep seeing 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str, devices: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices} "
            + os.environ.get("XLA_FLAGS", ""))
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == {devices}, jax.device_count()
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


def test_ring_allgather_matmul_matches_reference():
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.dist.collectives import (
            shard_map, ring_allgather_matmul, allgather_matmul_reference,
            ring_matmul_reducescatter, matmul_reducescatter_reference)

        mesh = make_test_mesh(data=1, model=8)
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
        w1 = jax.random.normal(jax.random.PRNGKey(1), (32, 48), jnp.float32)
        w2 = jax.random.normal(jax.random.PRNGKey(2), (48, 32), jnp.float32)

        def both(x_shard, w_col):
            a = ring_allgather_matmul(x_shard, w_col, "model")
            b = allgather_matmul_reference(x_shard, w_col, "model")
            return a, b

        f = jax.jit(shard_map(
            both, mesh=mesh,
            in_specs=(P("model", None), P(None, "model")),
            out_specs=(P(None, "model"), P(None, "model"))))
        a, b = f(x, w1)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(x @ w1),
                                   rtol=1e-4, atol=1e-4)

        def both2(h, w_row):
            a = ring_matmul_reducescatter(h, w_row, "model")
            b = matmul_reducescatter_reference(h, w_row, "model")
            return a, b

        h = jax.random.normal(jax.random.PRNGKey(3), (64, 48), jnp.float32)
        g = jax.jit(shard_map(
            both2, mesh=mesh,
            in_specs=(P(None, "model"), P("model", None)),
            out_specs=(P("model", None), P("model", None))))
        a2, b2 = g(h, w2)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(b2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(h @ w2),
                                   rtol=1e-4, atol=1e-4)
        print("collectives OK")
    """)


def test_overlapped_mlp_end_to_end():
    run_in_subprocess("""
        from repro.launch.mesh import make_test_mesh
        from repro.dist.collectives import make_overlapped_mlp

        mesh = make_test_mesh(data=1, model=8)
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        w1 = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        w2 = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
        fused = make_overlapped_mlp(mesh, overlap=True)(x, w1, w2)
        plain = make_overlapped_mlp(mesh, overlap=False)(x, w1, w2)
        want = jax.nn.relu(x @ w1) @ w2
        np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        print("overlapped MLP OK")
    """)


def test_overlap_replaces_allgather_with_permutes():
    """The fused path's HLO has collective-permutes instead of all-gathers —
    the structural evidence of overlap."""
    run_in_subprocess("""
        from repro.launch.mesh import make_test_mesh
        from repro.dist.collectives import make_overlapped_mlp

        mesh = make_test_mesh(data=1, model=8)
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        w1 = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        w2 = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
        fused_hlo = make_overlapped_mlp(mesh, overlap=True).lower(
            x, w1, w2).compile().as_text()
        plain_hlo = make_overlapped_mlp(mesh, overlap=False).lower(
            x, w1, w2).compile().as_text()
        assert "collective-permute" in fused_hlo
        assert "all-gather" in plain_hlo
        print("HLO structure OK")
    """)


def test_gdt_placement_on_sharded_params():
    """Tier migration composes with mesh sharding: a sharded array keeps its
    PartitionSpec across a host-tier roundtrip."""
    run_in_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(data=2, model=4)
        sh = NamedSharding(mesh, P("data", "model"))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh)
        host = jax.device_put(x, sh.with_memory_kind("pinned_host"))
        assert host.sharding.memory_kind == "pinned_host"
        assert host.sharding.spec == sh.spec
        back = jax.device_put(host, sh.with_memory_kind("device"))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        print("sharded tier roundtrip OK")
    """)
