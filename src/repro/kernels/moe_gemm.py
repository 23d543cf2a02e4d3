"""Grouped-expert SwiGLU GEMM over sorted ragged segments — Pallas TPU
kernel (MegaBlocks-style).

Input tokens arrive argsorted by group id, so each group owns one
contiguous ragged segment of rows; ``group_sizes`` gives the segment
lengths (empty segments allowed).  A *group* is usually an expert, but the
kernel decouples the two: ``group_experts`` maps each of the G groups to
the expert whose weights it multiplies, so the same kernel executes

* the classic per-expert layout (G == E, ``group_experts == arange(E)``),
* the per-batch-row dropless layout (G == B·E, expert ``g % E`` — keeps the
  dropless argsort shard-local over the data axis), and
* the ragged ep layout (G == m·E_local, one segment per (source shard,
  local expert) pair after the ragged all-to-all).

The kernel tiles the row dim into ``block_t`` physical tiles and walks a
sequence of *logical* tiles — one per (group, physical tile) pair the
group's segment overlaps.  A physical tile whose rows straddle a segment
boundary is visited once per overlapping group with a row-masked store, so
ragged boundaries need no padding of the token stream itself.

Grid = (logical_tiles, ff_tiles); the ff dim is innermost so the SwiGLU
partial products accumulate in a VMEM f32 scratch and the output tile is
written once, on the last ff step.  Per-logical-tile group ids, expert
(weight) ids, physical tile ids and segment offsets are scalar-prefetched
(SMEM) so the BlockSpec index maps can steer the expert-weight DMAs.

The logical-tile count depends on the (traced) group sizes, so the grid is
the static worst case ``row_tiles + G - 1``; surplus steps replay the last
tile with a row mask drawn from their own segment offsets, which makes them
idempotent rewrites or no-ops — never double-accumulation.

Backward: custom VJP recomputes through the jnp oracle (exact), mirroring
flash_attention.py — the fwd kernel is the serving hot spot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

F32 = jnp.float32
DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_F = 512
# VMEM for the double-buffered gate, up and down weight tiles of one grid
# step: the ff tile shrinks at wide d to keep them, with the row tiles and
# the accumulator, within the 16 MiB scoped VMEM.
WEIGHT_VMEM = 10 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _ff_block(f: int, block_f: int) -> int:
    """The ff tile: ``block_f`` (at most ``f`` rounded up to 128 lanes),
    stepped down by 128 lanes until it divides that rounding.  The expert
    weights then need no padding beyond it — a pad would copy every
    expert's weights (the whole HBM expert cache, when it is the operand)
    on every call — and the tile stays within VMEM at wide ``d`` (1,408 =
    11 x 128 gives 128)."""
    f128 = _round_up(f, 128)
    bf = min(block_f, f128)
    while f128 % bf and bf > 128:
        bf -= 128
    return bf


def _pad_axis(x, size: int, axis: int):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def make_group_metadata(group_sizes, rows: int, block_t: int):
    """Logical-tile schedule for a ragged row partition into G groups.

    Returns (group_ids, m_tile_ids, group_offsets):
      * group_ids[i]   — group handled by logical tile i,
      * m_tile_ids[i]  — physical row tile it reads/writes (non-decreasing),
      * group_offsets  — (G+1,) row offsets of the segments.
    Arrays are padded to the static worst-case length ``row_tiles + G - 1``;
    padded entries replay the last physical tile (idempotent, see module
    docstring).
    """
    G = group_sizes.shape[0]
    tiles_m = _round_up(rows, block_t) // block_t
    L = tiles_m + G - 1

    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    group_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)])
    first_tile = (starts // block_t).astype(jnp.int32)
    # Tiles overlapped by each segment; empty segments get none.
    spanned = (-(-ends // block_t)).astype(jnp.int32) - first_tile
    group_tiles = jnp.where(group_sizes > 0, spanned, 0)

    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), group_tiles,
                           total_repeat_length=L)
    tile_base = jnp.cumsum(group_tiles) - group_tiles   # exclusive cumsum
    m_tile_ids = (first_tile[group_ids]
                  + (jnp.arange(L, dtype=jnp.int32) - tile_base[group_ids]))
    m_tile_ids = jnp.clip(m_tile_ids, 0, tiles_m - 1)
    return group_ids, m_tile_ids, group_offsets


def _kernel(gids_ref, mids_ref, offs_ref, wids_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref, *, block_t: int, n_ff: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(F32)                        # (block_t, d)
    g = jax.lax.dot_general(x, wg_ref[0].astype(F32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)
    u = jax.lax.dot_general(x, wu_ref[0].astype(F32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)
    h = jax.nn.silu(g) * u                            # (block_t, block_f)
    acc_ref[...] += jax.lax.dot_general(h, wd_ref[0].astype(F32),
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=F32)

    @pl.when(j == n_ff - 1)
    def _store():
        gid = gids_ref[i]
        seg_start = offs_ref[gid]
        seg_end = offs_ref[gid + 1]
        row = mids_ref[i] * block_t + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mask = (row >= seg_start) & (row < seg_end)
        # First visit of a physical tile initializes it; later visits (other
        # groups sharing the tile) only overwrite their own rows.
        first = jnp.logical_or(
            i == 0, mids_ref[jnp.maximum(i - 1, 0)] != mids_ref[i])
        prev = jnp.where(first, jnp.zeros_like(acc_ref[...]), o_ref[...])
        o_ref[...] = jnp.where(mask, acc_ref[...], prev).astype(o_ref.dtype)


def _grouped_ffn_fwd(x, w_gate, w_up, w_down, group_sizes, group_experts, *,
                     block_t: int, block_f: int, interpret: bool):
    T, d = x.shape
    E, _, f = w_gate.shape
    G = group_sizes.shape[0]
    d_p = _round_up(d, 128)
    fit = WEIGHT_VMEM // (6 * d_p * w_gate.dtype.itemsize) // 128 * 128
    bf = _ff_block(f, max(128, min(block_f, fit)))
    f_p = _round_up(f, bf)
    T_p = _round_up(T, block_t)
    tiles_m = T_p // block_t
    L = tiles_m + G - 1
    n_ff = f_p // bf

    xp = _pad_axis(_pad_axis(x, T_p, 0), d_p, 1)
    wg = _pad_axis(_pad_axis(w_gate, d_p, 1), f_p, 2)
    wu = _pad_axis(_pad_axis(w_up, d_p, 1), f_p, 2)
    wd = _pad_axis(_pad_axis(w_down, f_p, 1), d_p, 2)

    gids, mids, offs = make_group_metadata(group_sizes, T_p, block_t)
    wids = group_experts.astype(jnp.int32)[gids]      # expert weights per tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(L, n_ff),
        in_specs=[
            pl.BlockSpec((block_t, d_p),
                         lambda i, j, gids, mids, offs, wids: (mids[i], 0)),
            pl.BlockSpec((1, d_p, bf),
                         lambda i, j, gids, mids, offs, wids:
                         (wids[i], 0, j)),
            pl.BlockSpec((1, d_p, bf),
                         lambda i, j, gids, mids, offs, wids:
                         (wids[i], 0, j)),
            pl.BlockSpec((1, bf, d_p),
                         lambda i, j, gids, mids, offs, wids:
                         (wids[i], j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_t, d_p),
            lambda i, j, gids, mids, offs, wids: (mids[i], 0)),
        scratch_shapes=[pltpu.VMEM((block_t, d_p), F32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, n_ff=n_ff),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T_p, d_p), x.dtype),
        interpret=interpret,
    )(gids, mids, offs, wids, xp, wg, wu, wd)
    return out[:T, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _grouped_ffn(x, w_gate, w_up, w_down, group_sizes, group_experts,
                 block_t, block_f, interpret):
    return _grouped_ffn_fwd(x, w_gate, w_up, w_down, group_sizes,
                            group_experts, block_t=block_t, block_f=block_f,
                            interpret=interpret)


def _ffn_fwd(x, w_gate, w_up, w_down, group_sizes, group_experts, block_t,
             block_f, interpret):
    out = _grouped_ffn(x, w_gate, w_up, w_down, group_sizes, group_experts,
                       block_t, block_f, interpret)
    return out, (x, w_gate, w_up, w_down, group_sizes, group_experts)


def _ffn_bwd(block_t, block_f, interpret, res, g):
    # Exact recompute backward via the jnp oracle (the fwd kernel is the
    # serving hot spot; numerics stay bit-comparable to the reference).
    x, w_gate, w_up, w_down, group_sizes, group_experts = res
    _, vjp = jax.vjp(
        lambda a, b, c, d: ref.moe_grouped_ffn_reference(
            a, b, c, d, group_sizes, group_experts),
        x, w_gate, w_up, w_down)
    dgs = np.zeros(group_sizes.shape, dtype=jax.dtypes.float0)
    dge = np.zeros(group_experts.shape, dtype=jax.dtypes.float0)
    return (*vjp(g), dgs, dge)


_grouped_ffn.defvjp(_ffn_fwd, _ffn_bwd)


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def moe_grouped_ffn_pallas(x, w_gate, w_up, w_down, group_sizes,
                           group_experts=None,
                           block_t: int = DEFAULT_BLOCK_T,
                           block_f: int = DEFAULT_BLOCK_F,
                           interpret: bool = False):
    """x: (T, d) sorted by group; w_gate/w_up: (E, d, f); w_down: (E, f, d);
    group_sizes: (G,) int32 summing to T; group_experts: (G,) int32 mapping
    each group to its expert weights (default arange — G == E).
    Returns (T, d)."""
    if group_experts is None:
        group_experts = jnp.arange(w_gate.shape[0], dtype=jnp.int32)
    return _grouped_ffn(x, w_gate, w_up, w_down,
                        group_sizes.astype(jnp.int32),
                        group_experts.astype(jnp.int32), block_t, block_f,
                        interpret)
