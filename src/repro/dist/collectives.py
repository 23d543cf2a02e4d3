"""Hand-scheduled ring collectives for compute/communication overlap.

The reference implementations use XLA's fused collectives (``all_gather`` /
``psum_scatter``): correct, but the gather must *complete* before the matmul
starts.  The ring variants decompose the collective into ``n-1`` point-to-
point ``ppermute`` steps interleaved with partial matmuls, so the compiler
can overlap each hop's transfer with the previous chunk's compute — the HLO
contains ``collective-permute`` ops instead of ``all-gather``.

All four kernels are written for use inside ``shard_map`` over one named
mesh axis.  ``psum(1, axis)`` is the standard static-axis-size idiom.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P


def _axis_size(axis_name: str) -> int:
    return jax.lax.psum(1, axis_name)


# ------------------------------------------------------- allgather-matmul
def allgather_matmul_reference(x_shard, w_col, axis_name: str):
    """out[:, col_shard] = allgather(x) @ w_col — the unfused baseline.

    ``x_shard``: (m, K) row shard of x; ``w_col``: (K, n_col) column shard.
    Returns the full-row (n*m, n_col) product for this device's columns.
    """
    x = jax.lax.all_gather(x_shard, axis_name, axis=0, tiled=True)
    return x @ w_col


def ring_allgather_matmul(x_shard, w_col, axis_name: str):
    """Ring-overlapped allgather+matmul: each step multiplies the chunk
    currently held and forwards it one hop around the ring."""
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_shard.shape[0]
    out_dtype = jnp.result_type(x_shard.dtype, w_col.dtype)
    out = jnp.zeros((n * m, w_col.shape[1]), out_dtype)
    perm = [(j, (j - 1) % n) for j in range(n)]  # receive from the right
    blk = x_shard
    for i in range(n):
        src = (idx + i) % n  # origin of the chunk currently held
        out = jax.lax.dynamic_update_slice(
            out, (blk @ w_col).astype(out_dtype), (src * m, 0))
        if i != n - 1:
            blk = jax.lax.ppermute(blk, axis_name, perm)
    return out


# --------------------------------------------------- matmul-reducescatter
def matmul_reducescatter_reference(h, w_row, axis_name: str):
    """scatter(psum(h @ w_row)) — the unfused baseline.

    ``h``: (M, k) column shard of activations; ``w_row``: (k, N) row shard.
    Returns this device's (M/n, N) row block of the summed product.
    """
    partial = h @ w_row
    return jax.lax.psum_scatter(
        partial, axis_name, scatter_dimension=0, tiled=True)


def ring_matmul_reducescatter(h, w_row, axis_name: str):
    """Ring-overlapped matmul+reduce-scatter: the partial sum destined for
    each device accumulates as it travels the ring."""
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = h.shape[0] // n
    k = h.shape[1]

    def contrib(dest):
        rows = jax.lax.dynamic_slice(h, (dest * m, 0), (m, k))
        return rows @ w_row

    perm = [(j, (j + 1) % n) for j in range(n)]  # send to the right
    acc = contrib((idx - 1) % n)
    for i in range(1, n):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + contrib((idx - i - 1) % n)
    return acc


# ------------------------------------------------- ragged all-to-all (ep MoE)
def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """shard_map without replication checking: the ragged collectives
    below produce outputs the checker cannot always prove replicated."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _exclusive_cumsum(sizes):
    c = jnp.cumsum(sizes)
    return (c - sizes).astype(jnp.int32)


def _place_chunk(out, chunk, offset, size, out_rows: int):
    """Deposit the first ``size`` rows of ``chunk`` at ``offset`` in ``out``.

    Invalid rows are zeroed and steered to index ``out_rows`` (dropped by
    the scatter), so a full-capacity chunk never clobbers a neighbouring
    block; each valid output row receives exactly one contribution, which
    makes the zero-initialized scatter-add exact."""
    i = jnp.arange(chunk.shape[0], dtype=jnp.int32)
    valid = i < size
    tgt = jnp.where(valid, offset + i, out_rows)
    return out.at[tgt].add(jnp.where(valid[:, None], chunk, 0), mode="drop")


def ring_ragged_all_to_all(rows, send_sizes, recv_sizes, axis_name: str, *,
                           chunk_rows: int, out_rows: int):
    """Dropless (ragged) all-to-all over one named axis, decomposed into
    ``n-1`` ``ppermute`` rotations of one static ``(chunk_rows, d)`` buffer
    — the ragged sibling of the ring kernels above.

    ``rows``: (R, d) send buffer with rows grouped contiguously by
    destination shard in ascending order; ``send_sizes``: (n,) rows
    destined to each peer (sum <= R); ``recv_sizes``: (n,) rows each peer
    sends here — the caller knows both from its routing metadata exchange
    (an all-gather of per-expert counts in the MoE ep path), so no extra
    size handshake happens here.

    ``chunk_rows`` bounds the rows any single peer pair exchanges (static);
    ``out_rows`` is the receive capacity.  Returns (out_rows, d) with the
    received rows packed contiguously, grouped by source shard in ascending
    order; slots beyond the ragged total stay zero.
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    d = rows.shape[1]
    send_offs = _exclusive_cumsum(send_sizes)
    recv_offs = _exclusive_cumsum(recv_sizes)
    # Pad the source so a full-capacity dynamic_slice near the ragged end
    # never clamps backwards into a neighbour's rows.
    src_buf = jnp.concatenate(
        [rows, jnp.zeros((chunk_rows, d), rows.dtype)], axis=0)
    out = jnp.zeros((out_rows, d), rows.dtype)

    def chunk_for(dest):
        return jax.lax.dynamic_slice(
            src_buf, (jnp.take(send_offs, dest), 0), (chunk_rows, d))

    # Self block: local copy, no hop.
    out = _place_chunk(out, chunk_for(idx), jnp.take(recv_offs, idx),
                       jnp.take(recv_sizes, idx), out_rows)
    for shift in range(1, n):
        dst = (idx + shift) % n
        src = (idx - shift) % n
        perm = [(j, (j + shift) % n) for j in range(n)]
        got = jax.lax.ppermute(chunk_for(dst), axis_name, perm)
        out = _place_chunk(out, got, jnp.take(recv_offs, src),
                           jnp.take(recv_sizes, src), out_rows)
    return out


def ragged_all_to_all_reference(rows, send_sizes, recv_sizes,
                                axis_name: str, *, chunk_rows: int,
                                out_rows: int):
    """Dense-gather oracle for ``ring_ragged_all_to_all``: all-gather every
    peer's full buffer and size table, then select this shard's blocks.
    Same contract, different data path (all-gather HLO instead of
    collective-permute) — the correctness anchor for the ring tests."""
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    d = rows.shape[1]
    all_rows = jax.lax.all_gather(rows, axis_name, axis=0)     # (n, R, d)
    all_sizes = jax.lax.all_gather(send_sizes, axis_name, axis=0)  # (n, n)
    all_offs = (jnp.cumsum(all_sizes, axis=1) - all_sizes).astype(jnp.int32)
    recv_offs = _exclusive_cumsum(recv_sizes)
    pad = jnp.zeros((chunk_rows, d), rows.dtype)
    out = jnp.zeros((out_rows, d), rows.dtype)
    for j in range(n):
        src = jnp.concatenate([all_rows[j], pad], axis=0)
        chunk = jax.lax.dynamic_slice(
            src, (all_offs[j, idx], 0), (chunk_rows, d))
        out = _place_chunk(out, chunk, jnp.take(recv_offs, j),
                           all_sizes[j, idx], out_rows)
    return out


# ------------------------------------------------------------ fused MLP
def make_overlapped_mlp(mesh, overlap: bool = True):
    """Jitted tensor-parallel MLP ``relu(x @ w1) @ w2`` over the ``model``
    axis.  ``overlap=True`` uses the ring kernels (collective-permute HLO);
    ``overlap=False`` uses the fused-collective references (all-gather HLO).
    """
    axis = "model"

    def mlp(x, w1, w2):
        if overlap:
            h = jax.nn.relu(ring_allgather_matmul(x, w1, axis))
            return ring_matmul_reducescatter(h, w2, axis)
        h = jax.nn.relu(allgather_matmul_reference(x, w1, axis))
        return matmul_reducescatter_reference(h, w2, axis)

    return jax.jit(shard_map(
        mlp, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis), P(axis, None)),
        out_specs=P(axis, None)))
