"""Tests for real-array tier enforcement via JAX memory kinds."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    ArenaBackend,
    ArenaManager,
    CLX,
    GuidanceConfig,
    GuidanceRuntime,
    SiteKind,
    SiteRegistry,
)
from repro.core.placement import JaxArenaPlacer, memory_kind_of

MB = 2**20


def build(cap_bytes, first_touch=False):
    reg = SiteRegistry()
    mgr = ArenaManager(
        reg,
        promotion_threshold=1024,
        fast_capacity_bytes=cap_bytes if first_touch else None,
    )
    placer = JaxArenaPlacer(mgr)
    gdt = GuidanceRuntime(
        ArenaBackend(mgr, CLX, placer=placer), CLX,
        GuidanceConfig(fast_capacity_bytes=cap_bytes, interval_steps=1),
    )
    return reg, mgr, placer, gdt


def test_bind_and_fetch_roundtrip():
    reg, mgr, placer, _ = build(1 << 30)
    s = reg.register(["w"], SiteKind.PARAM)
    x = jnp.arange(4096, dtype=jnp.float32)
    arena = mgr.allocate(s, x.size * 4)
    placer.bind(arena.arena_id, "w", x)
    got = placer.fetch_fast(arena.arena_id)["w"]
    assert (got == x).all()
    assert memory_kind_of(got) == "device"


def test_enforce_moves_memory_kind():
    """Cold data first-touches into HBM; the hot late-comer spills to host.
    Online guidance swaps their tiers once rental beats purchase."""
    reg, mgr, placer, gdt = build(cap_bytes=8192, first_touch=True)
    cold = reg.register(["cold"], SiteKind.PARAM)
    hot = reg.register(["hot"], SiteKind.PARAM)
    xc = jnp.ones((2048,), jnp.float32)   # 8 KB
    xh = jnp.ones((2048,), jnp.float32)   # 8 KB
    ac = mgr.allocate(cold, 8192)          # first -> all fast
    ah = mgr.allocate(hot, 8192)           # spills -> slow
    placer.bind(ac.arena_id, "w", xc)
    placer.bind(ah.arena_id, "w", xh)
    assert memory_kind_of(placer.get(ah.arena_id, "w")) == "pinned_host"
    # Drive accesses so 'hot' is recommended fast; capacity only fits one.
    for _ in range(6):
        mgr.touch(hot, 10_000_000)
        mgr.touch(cold, 1)
        gdt.on_step()
    kh = memory_kind_of(placer.get(ah.arena_id, "w"))
    kc = memory_kind_of(placer.get(ac.arena_id, "w"))
    assert kh == "device"
    assert kc == "pinned_host"
    # Values survive migration (fetch back to device kind to compare).
    back = placer.fetch_fast(ac.arena_id)["w"]
    assert (back == xc).all()


def test_fetch_fast_transfers_slow_entries():
    reg, mgr, placer, _ = build(1 << 30)
    s = reg.register(["x"], SiteKind.OPT_STATE)
    x = jnp.full((1024,), 3.0, jnp.float32)
    arena = mgr.allocate(s, 4096)
    placer.bind(arena.arena_id, "m", x)
    placer._apply(arena.arena_id, 0.0)  # demote everything
    assert memory_kind_of(placer.get(arena.arena_id, "m")) == "pinned_host"
    before = placer.transfers_bytes
    got = placer.fetch_fast(arena.arena_id)["m"]
    assert memory_kind_of(got) == "device"
    assert placer.transfers_bytes > before  # rental paid
    assert (got == 3.0).all()


def test_writeback_preserves_tier():
    reg, mgr, placer, _ = build(1 << 30)
    s = reg.register(["x"], SiteKind.OPT_STATE)
    arena = mgr.allocate(s, 4096)
    placer.bind(arena.arena_id, "m", jnp.zeros((1024,), jnp.float32))
    placer._apply(arena.arena_id, 0.0)
    new = jnp.full((1024,), 7.0, jnp.float32)
    placer.writeback(arena.arena_id, {"m": new})
    got = placer.get(arena.arena_id, "m")
    assert memory_kind_of(got) == "pinned_host"
    assert (jax.device_put(got) == 7.0).all()


def test_fractional_placement_array_granularity():
    reg, mgr, placer, _ = build(1 << 30)
    s = reg.register(["kv"], SiteKind.KV_CACHE)
    arena = mgr.allocate(s, 4 * 4096)
    for i in range(4):
        placer.bind(arena.arena_id, f"p{i}", jnp.zeros((1024,), jnp.float32))
    placer._apply(arena.arena_id, 0.5)
    kinds = [memory_kind_of(e.array) for e in placer.entries(arena.arena_id)]
    assert kinds == ["device", "device", "pinned_host", "pinned_host"]
    assert placer.fast_bytes() == 2 * 4096
    assert placer.slow_bytes() == 2 * 4096
