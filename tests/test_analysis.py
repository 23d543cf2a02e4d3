"""Tests for the loop-aware cost accounting (launch/analysis.py) — the
roofline's data source.  XLA's cost_analysis counts while bodies once; these
tests pin our corrected pipeline against analytic ground truth."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.analysis import collective_bytes, jaxpr_cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- jaxpr costs
def test_dot_flops_exact():
    def f(x, w):
        return x @ w

    jx = jax.make_jaxpr(f)(jnp.ones((64, 128)), jnp.ones((128, 32)))
    cost = jaxpr_cost(jx)
    assert cost["flops"] == 2 * 64 * 128 * 32


def test_scan_multiplies_body_cost():
    def f(x, ws):
        def body(x, w):
            return x @ w, None

        x, _ = jax.lax.scan(body, x, ws)
        return x

    for L in (1, 4, 16):
        jx = jax.make_jaxpr(f)(jnp.ones((8, 16)), jnp.ones((L, 16, 16)))
        cost = jaxpr_cost(jx)
        assert cost["flops"] == L * 2 * 8 * 16 * 16, L


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(x, w_outer):
            def inner(x, _):
                return x @ w_outer, None

            x, _ = jax.lax.scan(inner, x, None, length=3)
            return x, None

        x, _ = jax.lax.scan(outer, x, ws)
        return x

    jx = jax.make_jaxpr(f)(jnp.ones((4, 8)), jnp.ones((5, 8, 8)))
    cost = jaxpr_cost(jx)
    assert cost["flops"] == 5 * 3 * 2 * 4 * 8 * 8


def test_grad_and_remat_counted():
    """Remat-inclusive backward ~ 4x forward for a matmul chain (fwd+recomp
    + 2 bwd dots)."""
    w = jnp.ones((32, 32))

    def f(x):
        @jax.checkpoint
        def blk(x):
            return jnp.tanh(x @ w)

        def body(x, _):
            return blk(x), None

        x, _ = jax.lax.scan(body, x, None, length=6)
        return x.sum()

    fwd = jaxpr_cost(jax.make_jaxpr(f)(jnp.ones((16, 32))))["flops"]
    bwd = jaxpr_cost(jax.make_jaxpr(jax.grad(f))(jnp.ones((16, 32))))["flops"]
    dot = 2 * 16 * 32 * 32 * 6
    assert abs(fwd - dot) / dot < 0.2
    # grad jaxpr = fwd dot + recompute/transpose dots: ~3-4x the fwd cost
    assert 2.8 <= bwd / fwd <= 4.8


def test_gather_counts_gathered_bytes_not_pool():
    pool = jnp.zeros((1024, 256))      # 1 MB pool

    def f(idx):
        return pool[idx]

    jx = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.int32))
    cost = jaxpr_cost(jx)
    # 4 rows of 256 f32 = 4 KB; the 1 MB pool operand must not be charged.
    assert cost["bytes_dot"] < 64 * 1024


# ------------------------------------------------ HLO collective expansion
def _collect(devices, body):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_while_loop_collectives_expand_by_trip_count():
    out = _collect(8, """
        from repro.launch.analysis import collective_bytes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        L = 7
        def f(x, ws):
            def body(x, w):
                y = x @ w                     # contract sharded dim -> psum
                return y, None
            x, _ = jax.lax.scan(body, x, ws)
            return x
        xs = jax.ShapeDtypeStruct((16, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
        with mesh:
            c = jax.jit(
                f,
                in_shardings=(NamedSharding(mesh, P(None, "model")),
                              NamedSharding(mesh, P(None, "model", None))),
                out_shardings=NamedSharding(mesh, P(None, "model")),
            ).lower(xs, ws).compile()
        coll = collective_bytes(c.as_text())
        total = sum(v["bytes"] for v in coll.values())
        counts = sum(v["count"] for v in coll.values())
        print("BYTES", int(total), "COUNT", int(counts))
    """)
    bytes_, count = int(out.split()[1]), int(out.split()[3])
    # One collective per iteration, 7 iterations; each moves >= the partial
    # product (16x64 f32 = 4 KB result, possibly resharded pieces).
    assert count >= 7, out
    assert bytes_ >= 7 * 16 * 64 * 4 // 8, out


def test_direct_collectives_counted_once():
    out = _collect(8, """
        from repro.launch.analysis import collective_bytes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(axis=0, keepdims=True) + x, NamedSharding(mesh, P()))
        xs = jax.ShapeDtypeStruct((64, 32), jnp.float32)
        with mesh:
            c = jax.jit(f, in_shardings=NamedSharding(mesh, P("d", None)),
                        out_shardings=NamedSharding(mesh, P())) \
                .lower(xs).compile()
        coll = collective_bytes(c.as_text())
        print("COUNT", int(sum(v["count"] for v in coll.values())))
    """)
    assert int(out.split()[1]) >= 1


@pytest.fixture(scope="module")
def dryrun_train_artifact(tmp_path_factory):
    """The dry-run artifact the 6ND validation reads.  A full dry-run drop
    (results/dryrun/...) is preferred when present; otherwise the artifact
    is regenerated trace-only into a tmpdir — jaxpr costs are mesh- and
    compile-independent, so a 1x1 mesh on the test host reproduces the
    pod256 numbers exactly and the assertions always run (no silent skip)."""
    real = os.path.join(REPO, "results", "dryrun", "pod256",
                        "llama3_2_1b__train_4k.json")
    if os.path.exists(real):
        return real
    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import make_mesh

    outdir = str(tmp_path_factory.mktemp("dryrun") / "pod256")
    mesh = make_mesh((1, 1), ("data", "model"))
    rec = run_cell("llama3_2_1b", "train_4k", mesh, "pod256", outdir,
                   trace_only=True)
    assert rec["status"] == "ok", rec.get("error")
    return os.path.join(outdir, "llama3_2_1b__train_4k.json")


def test_flops_validation_against_6nd(dryrun_train_artifact):
    """The headline validation: full train step flops within 5% of the
    analytic remat-inclusive 8*N*D (also asserted in EXPERIMENTS.md)."""
    import json
    rec = json.load(open(dryrun_train_artifact))
    from repro.configs import get
    from repro.models import build_model
    from repro.models.common import count_params

    n = count_params(build_model(get("llama3_2_1b")).param_defs())
    analytic = 8 * n * 256 * 4096
    assert abs(rec["global_cost"]["flops"] - analytic) / analytic < 0.05
