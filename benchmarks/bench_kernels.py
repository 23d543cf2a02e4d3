"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle wall time on
CPU — correctness-weighted timing only (TPU wall-time is the target, not
measurable here); ``derived`` = max abs error vs the oracle."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.dist.collectives import (
    ragged_all_to_all_reference,
    ring_ragged_all_to_all,
    shard_map_unchecked,
)
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gemm import moe_grouped_ffn_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.launch.mesh import make_mesh

from .common import emit


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = False):
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    # flash attention
    B, S, H, K, dh = 2, 256, 8, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, dh), jnp.float32)
    want, us_ref = _time(lambda a, b, c: ref.mha_reference(a, b, c), q, k, v)
    got, us_pal = _time(
        lambda a, b, c: flash_attention_pallas(a, b, c, True, None, True),
        q, k, v)
    err = float(jnp.abs(got - want).max())
    rows.append(("kernels/flash_attention/oracle", us_ref, 0.0))
    rows.append(("kernels/flash_attention/pallas_interpret", us_pal, err))

    # paged attention
    rng = np.random.default_rng(0)
    B, H, K, dh, N, P, MP = 4, 8, 4, 64, 32, 16, 8
    q1 = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, P, K, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, P, K, dh)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(N)[: B * MP].reshape(B, MP), jnp.int32)
    lengths = jnp.asarray(rng.integers(P, MP * P, B), jnp.int32)
    want, us_ref = _time(ref.paged_attention_reference, q1, kp, vp, table,
                         lengths)
    got, us_pal = _time(
        lambda *a: paged_attention_pallas(*a, interpret=True),
        q1, kp, vp, table, lengths)
    err = float(jnp.abs(got - want).max())
    rows.append(("kernels/paged_attention/oracle", us_ref, 0.0))
    rows.append(("kernels/paged_attention/pallas_interpret", us_pal, err))

    # ssd scan
    B, Q, H, P_, N_ = 2, 128, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(B, Q, H, P_)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, Q, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, Q, N_)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, Q, N_)), jnp.float32)
    want, us_ref = _time(ref.ssd_reference, x, dt, A, Bm, Cm)
    got, us_pal = _time(lambda *a: ssd_scan_pallas(*a, interpret=True),
                        x, dt, A, Bm, Cm)
    err = float(jnp.abs(got - want).max())
    rows.append(("kernels/ssd_scan/oracle", us_ref, 0.0))
    rows.append(("kernels/ssd_scan/pallas_interpret", us_pal, err))

    # grouped-expert GEMM (dropless MoE dispatch): ragged per-expert
    # segments with an empty group, tile-straddling boundaries included.
    E, d, f = (4, 64, 128) if quick else (8, 128, 512)
    sizes = rng.integers(0, 96, E)
    sizes[0] = 0
    sizes[-1] = max(int(sizes[-1]), 1)
    T = int(sizes.sum())
    gs = jnp.asarray(sizes, jnp.int32)
    xg = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, f, d)) * 0.1, jnp.float32)
    want, us_ref = _time(ref.moe_grouped_ffn_reference, xg, wg, wu, wd, gs)
    got, us_pal = _time(
        lambda *a: moe_grouped_ffn_pallas(*a, block_t=64, block_f=128,
                                          interpret=True),
        xg, wg, wu, wd, gs)
    err = float(jnp.abs(got - want).max())
    rows.append(("kernels/moe_grouped_gemm/oracle", us_ref, 0.0))
    rows.append(("kernels/moe_grouped_gemm/pallas_interpret", us_pal, err))

    # ragged all-to-all (dropless ep MoE dispatch): ring ppermute
    # decomposition vs the dense all-gather oracle, over however many
    # devices this process has (CI's 8-device job makes it a real
    # exchange; on one device it degenerates to the local copy).
    n = jax.device_count()
    mesh = make_mesh((n,), ("model",))
    R, dr = (32, 64) if quick else (128, 256)
    sizes = rng.integers(1, max(R // n, 2), (n, n)).astype(np.int32)
    if n > 1:
        sizes[0, :] = 0                      # an empty-send shard
    payload = jnp.asarray(rng.normal(size=(n, R, dr)), jnp.float32)
    send = jnp.asarray(sizes)
    recv = jnp.asarray(np.ascontiguousarray(sizes.T))

    def _a2a(fn):
        def body(rows_blk, send_blk, recv_blk):
            return fn(rows_blk[0], send_blk[0], recv_blk[0], "model",
                      chunk_rows=R, out_rows=n * R)[None]
        spec = PartitionSpec("model")
        return jax.jit(shard_map_unchecked(
            body, mesh, in_specs=(spec, spec, spec), out_specs=spec))

    want, us_ref = _time(_a2a(ragged_all_to_all_reference), payload, send,
                         recv)
    got, us_ring = _time(_a2a(ring_ragged_all_to_all), payload, send, recv)
    err = float(jnp.abs(got - want).max())
    rows.append(("kernels/ragged_all_to_all/dense_oracle", us_ref, 0.0))
    rows.append(("kernels/ragged_all_to_all/ring", us_ring, err))
    return emit(rows)


if __name__ == "__main__":
    run()
