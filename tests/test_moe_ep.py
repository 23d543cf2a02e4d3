"""Ragged expert-parallel MoE dispatch on a multi-device CPU mesh.

Covers the ep-mode serving-correctness contract end to end: the ring
ragged all-to-all against both its dense-gather oracle and a pure-numpy
ground truth (empty send/recv shards included), layer-level parity of the
shard_map ep path against the meshless dropless path, chunking invariance
(batched prefill == chunked prefill == step-by-step decode) under the
mesh, empty-segment expert shards, the ep-axis config validation, and the
shard-locality guarantee of the per-row dropless argsort (a data-sharded
mesh compiles the tp dispatch with zero gather collectives).

Everything needing >1 device runs in a subprocess that sets
XLA_FLAGS=--xla_force_host_platform_device_count before importing jax
(same pattern as test_dist_collectives.py)."""

from test_dist_collectives import run_in_subprocess


def test_ring_ragged_all_to_all_matches_oracle_and_numpy():
    run_in_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.dist.collectives import (
            ring_ragged_all_to_all, ragged_all_to_all_reference,
            shard_map_unchecked)

        n = 8
        mesh = make_test_mesh(data=1, model=n)
        rng = np.random.default_rng(0)
        R, d = 24, 16
        # sizes[j, p] = rows shard j sends shard p; row sums stay <= R.
        sizes = rng.integers(0, R // n, (n, n)).astype(np.int32)
        sizes[2, :] = 0                      # a shard that sends nothing
        sizes[:, 5] = 0                      # a shard that receives nothing
        rows = rng.normal(size=(n, R, d)).astype(np.float32)
        recv_sizes = np.ascontiguousarray(sizes.T)
        out_rows = n * R

        def body(rows_blk, send_blk, recv_blk):
            a = ring_ragged_all_to_all(
                rows_blk[0], send_blk[0], recv_blk[0], "model",
                chunk_rows=R, out_rows=out_rows)
            b = ragged_all_to_all_reference(
                rows_blk[0], send_blk[0], recv_blk[0], "model",
                chunk_rows=R, out_rows=out_rows)
            return a[None], b[None]

        f = jax.jit(shard_map_unchecked(
            body, mesh,
            in_specs=(P("model"), P("model"), P("model")),
            out_specs=(P("model"), P("model"))))
        a, b = f(jnp.asarray(rows), jnp.asarray(sizes),
                 jnp.asarray(recv_sizes))
        a, b = np.asarray(a), np.asarray(b)

        for p in range(n):
            want = np.zeros((out_rows, d), np.float32)
            off = 0
            for j in range(n):
                o = int(sizes[j, :p].sum())
                cnt = int(sizes[j, p])
                want[off:off + cnt] = rows[j, o:o + cnt]
                off += cnt
            np.testing.assert_allclose(a[p], want, atol=0, rtol=0)
            np.testing.assert_allclose(b[p], want, atol=0, rtol=0)
        print("ragged a2a OK")
    """)


def test_ep_dropless_parity_and_chunking_invariance_on_mesh():
    """The shard_map ragged-ep path computes the same function as the
    meshless per-row dropless path, and under the mesh batched prefill,
    chunked prefill and step-by-step decode agree (ep serving no longer
    re-exposes the prefill/decode divergence the capacity pin caused)."""
    run_in_subprocess("""
        from repro.launch.mesh import make_test_mesh
        from repro.models.common import init_params
        from repro.models.moe import MoEConfig, moe, moe_decode, moe_defs

        cfg = MoEConfig(d_model=32, d_ff=48, n_experts=6, top_k=2,
                        parallelism="ep", ep_axis_size=4)
        assert cfg.dispatch == "dropless" and cfg.padded_experts == 8
        p = jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if a.dtype == jnp.bfloat16 else a,
            init_params(moe_defs(cfg), jax.random.PRNGKey(0)))
        rng = np.random.default_rng(2)
        B, S = 2, 12
        x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)

        y_local = np.asarray(moe(p, x, cfg))        # no mesh: fallback path

        mesh = make_test_mesh(data=2, model=4)
        run = jax.jit(lambda xx: moe(p, xx, cfg))
        dec = jax.jit(lambda xx: moe_decode(p, xx, cfg))
        with mesh:
            y_full = np.asarray(run(x))
            y_chunks = np.concatenate(
                [np.asarray(run(x[:, i:i + 4])) for i in range(0, S, 4)],
                axis=1)
            y_steps = np.concatenate(
                [np.asarray(dec(x[:, i:i + 1])) for i in range(S)], axis=1)
            # grads flow through the ragged all-to-alls (ppermute/scatter
            # transposes) the same as through the meshless path
            g_mesh = np.asarray(jax.jit(jax.grad(
                lambda xx: moe(p, xx, cfg).sum()))(x))
        g_local = np.asarray(jax.grad(lambda xx: moe(p, xx, cfg).sum())(x))

        np.testing.assert_allclose(y_full, y_local, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(y_chunks, y_full, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(y_steps, y_full, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(g_mesh, g_local, atol=1e-4, rtol=1e-4)
        print("ep parity OK")
    """)


def test_ep_dropless_empty_expert_shards():
    """Shards whose experts attract zero tokens exchange empty ragged
    segments (size-0 all-to-all blocks) without corrupting neighbours."""
    run_in_subprocess("""
        from repro.launch.mesh import make_test_mesh
        from repro.models.common import init_params
        from repro.models.moe import MoEConfig, moe, moe_defs

        cfg = MoEConfig(d_model=32, d_ff=48, n_experts=6, top_k=2,
                        parallelism="ep", ep_axis_size=4)
        p = jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if a.dtype == jnp.bfloat16 else a,
            init_params(moe_defs(cfg), jax.random.PRNGKey(1)))
        # Bias routing so only experts 0 and 1 are ever picked: shards
        # owning experts 2..7 receive nothing at all.
        router = np.array(p["router"])
        router[:, 2:] = -30.0
        p = {**p, "router": jnp.asarray(router)}

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
        y_local = np.asarray(moe(p, x, cfg))
        mesh = make_test_mesh(data=2, model=4)
        with mesh:
            y_mesh = np.asarray(jax.jit(lambda xx: moe(p, xx, cfg))(x))
        np.testing.assert_allclose(y_mesh, y_local, atol=1e-5, rtol=1e-5)
        print("empty shards OK")
    """)


def test_ep_axis_mismatch_raises_under_mesh():
    """A pad target that doesn't divide over the live model axis fails
    loudly at trace time, not as a shape error deep in the all-to-all."""
    run_in_subprocess("""
        from repro.launch.mesh import make_test_mesh
        from repro.models.common import init_params
        from repro.models.moe import MoEConfig, moe, moe_defs

        cfg = MoEConfig(d_model=32, d_ff=48, n_experts=6, top_k=2,
                        parallelism="ep", ep_axis_size=2)   # padded to 6
        p = init_params(moe_defs(cfg), jax.random.PRNGKey(0))
        x = jnp.zeros((2, 4, cfg.d_model), jnp.float32)
        mesh = make_test_mesh(data=2, model=4)              # 6 % 4 != 0
        try:
            with mesh:
                jax.jit(lambda xx: moe(p, xx, cfg))(x)
        except ValueError as e:
            assert "ep mesh mismatch" in str(e), e
            print("validation OK")
        else:
            raise AssertionError("expected ep mesh mismatch ValueError")
    """)


def test_per_row_dispatch_compiles_shard_local_on_data_mesh():
    """The acceptance check for the per-row argsort: tp-dropless lowered on
    a purely data-sharded mesh all-gathers NO float data — activations and
    routing probs (the token stream) stay inside their batch shard, and no
    all-to-all appears at all.  The old flat B*S*k argsort gathered the
    whole token stream across data shards.  (Tiny int32 segment-offset
    cumsums inside the grouped-FFN oracle may still gather: they are
    d_model*dtype-times smaller than the activation gathers this test
    guards against.)"""
    run_in_subprocess("""
        import re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.models.common import init_params
        from repro.models.moe import MoEConfig, moe, moe_defs

        cfg = MoEConfig(d_model=32, d_ff=48, n_experts=6, top_k=2)
        p = jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if a.dtype == jnp.bfloat16 else a,
            init_params(moe_defs(cfg), jax.random.PRNGKey(0)))
        mesh = make_test_mesh(data=8, model=1)
        x = jnp.zeros((8, 16, cfg.d_model), jnp.float32)
        with mesh:
            lowered = jax.jit(
                lambda pp, xx: moe(pp, xx, cfg),
                in_shardings=(
                    jax.tree.map(
                        lambda a: NamedSharding(mesh, P()), p),
                    NamedSharding(mesh, P("data", None, None))),
            ).lower(p, x)
            hlo = lowered.compile().as_text()
        assert "all-to-all" not in hlo
        float_gathers = [
            ln.strip() for ln in hlo.splitlines()
            if re.search(r"= (f32|bf16|f16)\\[[0-9,]*\\][^=]*all-gather",
                         ln)]
        assert not float_gathers, float_gathers
        print("shard-local OK")
    """)
