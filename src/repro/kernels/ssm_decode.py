"""Mamba-2 decode step over a pool of recurrent states — Pallas TPU
kernel.

One token per row: each row's SSM state of one layer is read once,
decayed and updated with the token, contracted with C, and written back
once, in place (the pool is aliased to the output):

    h[n, l] <- exp(dt[l] A[l]) h[n, l] + B[n] dt[l] x[l]
    y[l]     = sum_n C[n] h[n, l] + D[l] x[l]

where a lane ``l`` is (head h, head lane p) of the flat (N, H*P) state
row, and dt, A and D are the head's values repeated over its P lanes.
State lanes run along sublanes and head lanes along lanes, so a state
block is lane-dense whatever the head width.

Grid = (rows, lane blocks).  The pool is (layers, slots, N, H*P) float32;
the layer and each row's slot are scalar-prefetched and steer the state
block's DMA, so rows read and write their own slots and nothing else of
the pool moves.  Rows that share a slot (a batch's inactive rows all
point at one scratch slot) may leave either row's state there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(layer_ref, slot_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
            s_ref, y_ref, so_ref):
    x = x_ref[0]                                   # (1, bl)
    dt = dt_ref[0]                                 # (1, bl)
    new = (jnp.exp(dt * a_ref[...]) * s_ref[0, 0]  # (N, bl)
           + b_ref[0, 0] * (dt * x))               # (N, 1) x (1, bl)
    so_ref[0, 0] = new
    y_ref[0] = (jnp.sum(c_ref[0, 0] * new, axis=0, keepdims=True)
                + d_ref[...] * x)


def _lane_block(lanes: int, block: int) -> int:
    """The largest multiple of 128 lanes, at most ``block``, dividing
    ``lanes`` (a group's lanes); ``lanes`` itself when it is no multiple
    of 128."""
    if lanes % 128:
        return lanes
    bl = min(block, lanes) // 128 * 128
    while lanes % bl:
        bl -= 128
    return bl


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_decode_pallas(pool, layer, slots, x, dt, A, D, Bm, Cm,
                      block: int = 2048, interpret: bool = False):
    """pool (Lm, slots, N, H*P) f32; layer () int32; slots (R,) int32;
    x (R, H*P); dt (R, H) f32 after softplus; A, D (H,) f32; Bm, Cm (R, G,
    N).  Returns (y (R, H*P) f32, the pool with layer ``layer``'s rows
    ``slots`` advanced one token)."""
    _, _, N, HP = pool.shape
    R, H = dt.shape
    P = HP // H
    G = Bm.shape[1]
    per_group = HP // G
    bl = _lane_block(per_group, block)
    assert G == 1 or bl % 128 == 0, "a lane block lies in one group"

    def lanes(a):                                   # (.., H) -> (.., H*P)
        return jnp.repeat(a.astype(F32), P, axis=-1)

    args = (jnp.reshape(layer, (1,)).astype(jnp.int32),
            slots.astype(jnp.int32),
            x.astype(F32).reshape(R, 1, HP), lanes(dt).reshape(R, 1, HP),
            lanes(A).reshape(1, HP), lanes(D).reshape(1, HP),
            Bm.astype(F32).reshape(R, G, N, 1),
            Cm.astype(F32).reshape(R, G, N, 1), pool)

    def row(r, j, lay, sl):
        return (r, 0, j)

    def head(r, j, lay, sl):
        return (0, j)

    def group(r, j, lay, sl):
        return (r, (j * bl) // per_group, 0, 0)

    def state(r, j, lay, sl):
        return (lay[0], sl[r], 0, j)

    y, pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # layer, slots
            grid=(R, HP // bl),
            in_specs=[
                pl.BlockSpec((1, 1, bl), row),
                pl.BlockSpec((1, 1, bl), row),
                pl.BlockSpec((1, bl), head),
                pl.BlockSpec((1, bl), head),
                pl.BlockSpec((1, 1, N, 1), group),
                pl.BlockSpec((1, 1, N, 1), group),
                pl.BlockSpec((1, 1, N, bl), state),
            ],
            out_specs=[pl.BlockSpec((1, 1, bl), row),
                       pl.BlockSpec((1, 1, N, bl), state)],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, 1, HP), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={len(args) - 1: 1},
        interpret=interpret,
    )(*args)
    return y.reshape(R, HP), pool


def _copy_kernel(layer_ref, slot_ref, src_ref, *refs):
    refs[-1][...] = src_ref[...].reshape(refs[-1].shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_state_read_pallas(pool, layer, slot, block: int = 2048,
                          interpret: bool = False):
    """Row ``slot`` of layer ``layer`` of a state pool (Lm, slots, N, H*P),
    as (N, H*P).  Through a kernel so that the pool keeps the layout it
    has outside (XLA would otherwise lay the whole pool out the way the
    prefill's scan reads one state, copying it)."""
    _, _, N, HP = pool.shape
    bl = _lane_block(HP, block)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(HP // bl,),
            in_specs=[pl.BlockSpec((1, 1, N, bl),
                                   lambda j, lay, sl: (lay[0], sl[0], 0, j))],
            out_specs=pl.BlockSpec((N, bl), lambda j, lay, sl: (0, j))),
        out_shape=jax.ShapeDtypeStruct((N, HP), pool.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(slot, (1,)).astype(jnp.int32), pool)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ssm_state_write_pallas(pool, layer, slot, state, block: int = 2048,
                           interpret: bool = False):
    """The pool with row ``slot`` of layer ``layer`` set to ``state`` (N,
    H*P), in place (the pool is aliased to the output)."""
    _, _, N, HP = pool.shape
    bl = _lane_block(HP, block)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(HP // bl,),
            in_specs=[pl.BlockSpec((N, bl), lambda j, lay, sl: (0, j)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, N, bl),
                                   lambda j, lay, sl: (lay[0], sl[0], 0, j))),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(slot, (1,)).astype(jnp.int32),
      state.astype(pool.dtype), pool)
