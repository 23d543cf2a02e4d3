"""Mixture-of-Experts layer (Mixtral / granite-MoE style).

Two dispatch modes (``MoEConfig.dispatch``):

* ``dropless`` (default, both parallelism modes): sorted ragged routing.
  Tokens are argsorted by expert id into contiguous per-expert segments and
  the expert SwiGLU runs as a grouped GEMM over the ragged segments
  (``kernels/moe_gemm.py`` on TPU, a masked-einsum oracle elsewhere).  No
  token is ever dropped, so the layer computes the *same function* for
  batched prefill, chunked prefill and single-token decode — routing is
  per-token and chunking-invariant.  The argsort is *per batch row* (one
  ragged segment per (row, expert) pair), so on a batch-sharded mesh every
  dispatch structure stays shard-local — no cross-data-shard gather.

* ``capacity``: GShard-style capacity-bounded scatter dispatch (tokens over
  capacity are dropped).  Retained as an explicit opt-in for comparison
  benchmarks; nothing pins it anymore.

Parallelism modes:
* ``tp`` (default): expert FFN hidden dim sharded over the model axis; the
  dispatch buffers shard over data via the token dim.  Works for any expert
  count (40 experts on a 16-way axis included).
* ``ep``: experts sharded over the model axis; expert count is padded up to
  a multiple of the axis with *dead* experts that the router masks to zero
  probability (semantics preserved exactly).  Under an active multi-device
  mesh, dropless ep dispatch/combine run as ragged (dropless) all-to-alls
  inside an explicit shard_map: per-shard ``group_sizes`` metadata is
  exchanged with one tiny all-gather, row payloads move over
  ``ring_ragged_all_to_all`` (ppermute hops), and each shard runs the
  grouped GEMM over its local (source shard x local expert) ragged
  segments.  Routing flows through the same ``route_tokens`` as every
  other path, so ep prefill, chunked prefill and decode agree exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..dist.collectives import ring_ragged_all_to_all, shard_map_unchecked
from ..dist.sharding import active_mesh, batch_data_axes, constrain
from ..kernels import ops
from .common import ArrayDef

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.0
    dispatch: str = "dropless"       # "dropless" | "capacity"
    parallelism: str = "tp"          # "tp" | "ep"
    ep_axis_size: int = 16           # ep pad target; validated vs the mesh

    @property
    def padded_experts(self) -> int:
        if self.parallelism != "ep":
            return self.n_experts
        m = self.ep_axis_size
        return ((self.n_experts + m - 1) // m) * m

    def validate_ep_axis(self, axis_size: int) -> None:
        """``ep_axis_size`` is a config constant decoupled from the mesh it
        eventually runs on (it fixes parameter shapes at init time), so call
        sites that see the real mesh must check the two agree: the model
        axis has to divide the padded expert count evenly or some shards
        would own a different number of experts than others."""
        if self.parallelism != "ep":
            return
        if axis_size <= 0 or self.padded_experts % axis_size != 0:
            raise ValueError(
                f"ep mesh mismatch: padded_experts={self.padded_experts} "
                f"(n_experts={self.n_experts} padded to ep_axis_size="
                f"{self.ep_axis_size}) does not divide evenly over a "
                f"{axis_size}-way model axis; set ep_axis_size to a "
                f"multiple of the mesh's model-axis size")


def moe_defs(cfg: MoEConfig):
    E = cfg.padded_experts
    expert_axis = "experts" if cfg.parallelism == "ep" else None
    mlp_axis = None if cfg.parallelism == "ep" else "mlp"
    return {
        "router": ArrayDef((cfg.d_model, E), ("embed", None), dtype=F32),
        "w_gate": ArrayDef((E, cfg.d_model, cfg.d_ff),
                           (expert_axis, "embed", mlp_axis)),
        "w_up": ArrayDef((E, cfg.d_model, cfg.d_ff),
                         (expert_axis, "embed", mlp_axis)),
        "w_down": ArrayDef((E, cfg.d_ff, cfg.d_model),
                           (expert_axis, mlp_axis, "embed")),
    }


# ================================================================= routing
def route_tokens(router, x2d, cfg: MoEConfig) -> Tuple[jax.Array, jax.Array]:
    """Per-token top-k routing: (T, d) -> (gates (T, k) f32, experts (T, k)).

    This is THE routing function — prefill, chunked prefill and decode all
    call it on their flattened token sets (the ep shard_map path included).
    It looks at one token at a time (softmax over experts, top-k,
    renormalize), so the token->expert assignment is bitwise-identical no
    matter how the token stream is chunked into batches.
    """
    E = cfg.padded_experts
    logits = jnp.einsum("td,de->te", x2d.astype(F32), router)
    if E != cfg.n_experts:  # mask dead padding experts (ep mode)
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    # Top-k as k iterative argmaxes — selection and order identical to
    # jax.lax.top_k (descending value, ties to the lowest index), but it
    # lowers to plain reduces the SPMD partitioner keeps shard-local,
    # where the TopK custom-call all-gathers the (T, E) probs on a
    # token-sharded mesh.  k and E are small; the passes are noise next
    # to the expert FFN.
    remaining = probs
    gate_cols, expert_cols = [], []
    for _ in range(cfg.top_k):
        e = jnp.argmax(remaining, axis=-1)
        gate_cols.append(
            jnp.take_along_axis(remaining, e[:, None], axis=-1)[:, 0])
        expert_cols.append(e.astype(jnp.int32))
        remaining = jnp.where(
            jnp.arange(E)[None, :] == e[:, None], -jnp.inf, remaining)
    gates = jnp.stack(gate_cols, axis=-1)                       # (T, k)
    experts = jnp.stack(expert_cols, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts


# ========================================================= dropless dispatch
def _sort_picks_by_expert(experts, k: int):
    """Stable per-set argsort of the flattened (n*k,) expert picks.

    Returns (order, tok_idx): ``order`` permutes pick-rows into contiguous
    ascending-expert segments, ``tok_idx`` is each sorted row's source
    token.  Stability keeps stream order within an expert's segment and
    each token's k contributions combining in ascending-expert order —
    both independent of batch chunking."""
    order = jnp.argsort(experts, stable=True)
    return order, order // k


def _data_sharded() -> bool:
    """True when the ambient mesh splits the batch over data axes — the
    regime where per-row dispatch structures pay for themselves."""
    mesh = active_mesh()
    if mesh is None:
        return False
    return any(int(mesh.shape[a]) > 1 for a in batch_data_axes(mesh))


def apply_dropless_flat(gates, experts, x, w_gate, w_up, w_down,
                        cfg: MoEConfig, expert_slots=None):
    """Flat dropless dispatch AFTER routing: sort the (B*S*k,) picks into
    contiguous per-expert ragged segments, run the grouped SwiGLU GEMM,
    combine gate-weighted in ascending-expert order.

    Factored out of ``_moe_dropless`` so the serving engine's tiered
    expert path (``serve/expert_store.py``) executes the *exact same op
    sequence* as the resident path — the bitwise-equality bar for expert
    tiering rests on this sharing.  ``expert_slots`` is an (E,) int32 map
    from logical expert id to the weight row holding its block; it rides
    the grouped GEMM's existing ``group_experts`` remap, so the weight
    arrays may carry more (or differently ordered) rows than ``cfg`` has
    experts — a bounded HBM cache.  Expert FFNs are row-independent, so
    the result is bitwise-identical to the dense layout whenever the
    referenced rows hold the same bytes.  ``expert_slots=None`` preserves
    the classic dense layout (row i == expert i) unchanged.
    """
    B, S, d = x.shape
    E = cfg.padded_experts
    k = cfg.top_k
    Sk = S * k
    flat_e = experts.reshape(B * Sk)
    order, tok_idx = _sort_picks_by_expert(flat_e, k)
    xs = jnp.take(x.reshape(B * S, d), tok_idx, axis=0)         # (B*Sk, d)
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    slots = None if expert_slots is None else expert_slots.astype(jnp.int32)
    ys = ops.moe_grouped_ffn(xs, w_gate, w_up, w_down, group_sizes, slots)
    gs = gates.reshape(B * Sk)[order]                           # f32
    y = jnp.zeros((B * S, d), F32).at[tok_idx].add(
        ys.astype(F32) * gs[:, None])
    return constrain(y.astype(x.dtype).reshape(B, S, d),
                     ("batch", "seq", "embed"))


def _moe_dropless(p, x, cfg: MoEConfig, per_row: Optional[bool] = None):
    """Sorted ragged dispatch: no capacity, no drops.

    Two segment layouts computing the identical per-token function (expert
    FFNs are row-independent and each token's k contributions combine in
    ascending-expert order under both):

    * **per-row** (picked when a data-sharded mesh is active): each batch
      row argsorts its own S*k picks, giving one contiguous ragged segment
      per (row, expert) pair; the grouped SwiGLU GEMM runs over all B*E
      segments at once (``group_experts`` maps segment -> expert weights)
      and a vmapped gate-weighted scatter-add restores token order.
      Keeping the sort, bincount and scatter *inside* the row makes the
      batch dim a pure batching dim for GSPMD — every dispatch structure
      stays shard-local, where a flat B*S*k sort gathers the whole token
      stream across data shards (prefill_32k dry-run collective bytes).

    * **flat** (meshless / undivided batch): one stable argsort over the
      flat B*S*k picks into E per-expert segments.  Same math, but the
      grouped GEMM's static logical-tile grid is row_tiles + E - 1 instead
      of row_tiles + B*E - 1 — decode at B=64, E=48 would otherwise pay
      ~60x the grid steps for shard-locality no single device needs.
    """
    B, S, d = x.shape
    E = cfg.padded_experts
    k = cfg.top_k
    Sk = S * k

    gates, experts = route_tokens(p["router"], x.reshape(B * S, d), cfg)
    if per_row is None:
        per_row = _data_sharded()

    if not per_row:
        return apply_dropless_flat(gates, experts, x, p["w_gate"],
                                   p["w_up"], p["w_down"], cfg)

    experts_r = experts.reshape(B, Sk)
    gates_r = gates.reshape(B, Sk)

    order, tok_in_row = jax.vmap(
        lambda e: _sort_picks_by_expert(e, k))(experts_r)       # (B, Sk)
    xs = jnp.take_along_axis(x, tok_in_row[..., None], axis=1)  # (B, Sk, d)
    group_sizes = jax.vmap(
        lambda e: jnp.bincount(e, length=E))(experts_r)         # (B, E)

    ys = ops.moe_grouped_ffn(
        xs.reshape(B * Sk, d), p["w_gate"], p["w_up"], p["w_down"],
        group_sizes.reshape(B * E).astype(jnp.int32),
        jnp.tile(jnp.arange(E, dtype=jnp.int32), B))            # (B*Sk, d)

    gs = jnp.take_along_axis(gates_r, order, axis=1)            # f32

    def row_combine(ys_row, tok_row, g_row):
        return jnp.zeros((S, d), F32).at[tok_row].add(
            ys_row.astype(F32) * g_row[:, None])

    y = jax.vmap(row_combine)(ys.reshape(B, Sk, d), tok_in_row, gs)
    y = y.astype(x.dtype)
    return constrain(y, ("batch", "seq", "embed"))


# ==================================================== ragged ep dispatch
def _ep_mesh(cfg: MoEConfig):
    """The active mesh when the explicit ragged-ep shard_map path applies
    (ep parallelism on a real multi-device model axis), else None."""
    if cfg.parallelism != "ep":
        return None
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.shape:
        return None
    if int(mesh.shape["model"]) <= 1:
        return None
    return mesh


def _moe_dropless_ep(p, x, cfg: MoEConfig, mesh):
    """Ragged (dropless) expert-parallel dispatch: all-to-alls carry exactly
    the routed rows, no capacity buffers, no drops.

    Inside one shard_map over the mesh (batch over the data axes, experts
    over ``model``), each model shard:

      1. takes its static slice of the local token stream and routes it
         through ``route_tokens`` (identical assignment to every other
         path; slice-padding rows get gate 0 and contribute nothing),
      2. argsorts its picks by global expert id — segments are contiguous
         per destination shard because each shard owns a contiguous expert
         range — and bincounts per-expert ``group_sizes``,
      3. exchanges the (E,) count vectors with one tiny all-gather (the
         metadata exchange), from which both sides of every ragged
         transfer size are known,
      4. moves row payloads with ``ring_ragged_all_to_all`` (ppermute
         hops), runs the grouped GEMM over its local (source shard x local
         expert) ragged segments via ``group_experts``, and sends results
         back over the reverse ragged all-to-all (same function, sizes
         swapped),
      5. combines with gates in ascending-expert order per token (the same
         order as the tp path) and all-gathers the token slices.

    Per-token math is identical to ``_moe_dropless``: expert FFNs are
    row-independent and combine order is fixed, so ep prefill, chunked
    prefill and decode agree with each other and with tp-dropless.
    """
    B, S, d = x.shape
    E = cfg.padded_experts
    k = cfg.top_k
    m = int(mesh.shape["model"])
    cfg.validate_ep_axis(m)
    E_loc = E // m

    # Batch shards over the data axes when it divides evenly (the shared
    # shed-until-divisible rule); everything else is replicated in.
    dp = batch_data_axes(mesh, B)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    B_loc = B // dp_size
    batch_entry = tuple(dp) if len(dp) > 1 else (dp[0] if dp else None)
    x_spec = P(batch_entry, None, None)

    T = B_loc * S                 # tokens per data shard
    Tm = -(-T // m)               # static per-model-shard token slice
    Rm = Tm * k                   # ragged-a2a chunk capacity: one slice's picks

    def body(xb, router, wg, wu, wd):
        e_idx = jax.lax.axis_index("model")
        xt = jnp.pad(xb.reshape(T, d), ((0, m * Tm - T), (0, 0)))
        my = jax.lax.dynamic_slice(xt, (e_idx * Tm, 0), (Tm, d))
        live = (e_idx * Tm + jnp.arange(Tm)) < T      # slice-padding rows
        gates, experts = route_tokens(router, my, cfg)          # (Tm, k)
        gates = gates * live[:, None].astype(gates.dtype)

        flat_e = experts.reshape(Rm)
        order, tok_idx = _sort_picks_by_expert(flat_e, k)
        xs = jnp.take(my, tok_idx, axis=0)                      # (Rm, d)
        counts = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        send_sizes = counts.reshape(m, E_loc).sum(axis=1)       # (m,)

        # Metadata exchange: every shard learns every peer's per-expert
        # counts, so both directions of the ragged transfers are sized
        # locally — no per-row size handshake.
        all_counts = jax.lax.all_gather(counts, "model", axis=0)  # (m, E)
        my_counts = jax.lax.dynamic_slice(
            all_counts, (0, e_idx * E_loc), (m, E_loc))
        recv_sizes = my_counts.sum(axis=1)                      # (m,)

        recv = ring_ragged_all_to_all(
            xs, send_sizes, recv_sizes, "model",
            chunk_rows=Rm, out_rows=m * Rm)                     # (m*Rm, d)

        # Shard-local grouped GEMM over (source shard, local expert) ragged
        # segments; group_experts folds the m-fold segment layout onto the
        # shard's E_loc expert weights.
        ys = ops.moe_grouped_ffn(
            recv, wg, wu, wd, my_counts.reshape(m * E_loc),
            jnp.tile(jnp.arange(E_loc, dtype=jnp.int32), m))

        # Combine leg: the receive layout (grouped by source) is exactly
        # the send layout of the reverse transfer, so rows come back in
        # the order this shard sent them (ascending expert id).
        back = ring_ragged_all_to_all(
            ys, recv_sizes, send_sizes, "model",
            chunk_rows=Rm, out_rows=Rm)                         # (Rm, d)

        gs = gates.reshape(Rm)[order]
        y_my = jnp.zeros((Tm, d), F32).at[tok_idx].add(
            back.astype(F32) * gs[:, None])
        y = jax.lax.all_gather(y_my, "model", axis=0, tiled=True)
        return y[:T].reshape(B_loc, S, d).astype(x.dtype)

    out = shard_map_unchecked(
        body, mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=x_spec,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return constrain(out, ("batch", "seq", "embed"))


# ========================================================= capacity dispatch
def _capacity(tokens: int, cfg: MoEConfig) -> int:
    """True per-row expert capacity: ceil(S*k/E_live * capacity_factor),
    floored at ``top_k``.

    The divisor is the *live* expert count: ep padding experts are masked
    to zero routing probability, so budgeting capacity over
    ``padded_experts`` silently shrank every live expert's slots to
    ~n_experts/padded_experts of what ``capacity_factor`` promises (40
    experts padded to 48 lost 17%).  The floor is the explicit, documented
    minimum (a row can always place one full token's worth of picks) that
    replaces the old magic ``max(8, ...)``; above it ``capacity_factor``
    is honored exactly, and buffer padding is layout-only (see
    ``_padded_capacity``)."""
    assert cfg.capacity_factor > 0, cfg.capacity_factor
    cap = int(np.ceil(tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(cap, cfg.top_k)


def _padded_capacity(cap: int) -> int:
    """Buffer-layout padding: round the slot dim up to a multiple of 8
    (TPU sublane alignment).  Padding slots are *dead* — the drop decision
    (``slot < cap``) uses the true capacity, so padding never silently
    admits tokens beyond what ``capacity_factor`` allows."""
    return -(-cap // 8) * 8


def _moe_capacity(p, x, cfg: MoEConfig):
    """GShard-style capacity-bounded dispatch; dropped tokens pass through
    (residual).

    Dispatch is *per batch row* (GShard's per-group capacity): slot
    assignment (cumsum), scatter and gather all happen within a row, so on a
    batch-sharded mesh every dispatch structure stays shard-local — no
    collective is needed beyond the expert matmuls' own sharding.  (A global
    dispatch here costs TiBs of all-reduce per step; see EXPERIMENTS.md
    §Perf climb #2.)
    """
    B, S, d = x.shape
    E = cfg.padded_experts
    cap = _capacity(S, cfg)                                     # per row
    C = _padded_capacity(cap)                                   # buffer slots
    Tk = S * cfg.top_k

    gate_vals, expert_ids = route_tokens(
        p["router"], x.reshape(B * S, d), cfg)
    gate_vals = gate_vals.reshape(B, S, cfg.top_k)
    expert_ids = expert_ids.reshape(B, S, cfg.top_k)

    # Slot assignment within each row: running count of earlier picks of the
    # same expert.  int16 is enough (C < 32768 at these shapes) and halves
    # the cumsum buffer.
    flat_e = expert_ids.reshape(B, Tk)                           # (B, Tk)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int16)          # (B, Tk, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
    slot = jnp.take_along_axis(
        pos_in_e, flat_e[..., None].astype(jnp.int32), axis=2)[..., 0]
    slot = slot.astype(jnp.int32)
    in_cap = slot < cap                # drop rule: true capacity, not padded

    # Scatter tokens into per-row (E, C, d) buffers.  vmap over rows keeps
    # the batch dim a *batching* dim of the scatter (GSPMD partitions it);
    # indexing it with an arange would make it a scattered dim and force the
    # partitioner to replicate + all-reduce the whole buffer.
    xk = jnp.repeat(x, cfg.top_k, axis=1)                        # (B, Tk, d)
    upd = jnp.where(in_cap[..., None], xk, 0).astype(x.dtype)
    safe_slot = jnp.where(in_cap, slot, C - 1)

    def row_scatter(e_row, s_row, u_row):
        return jnp.zeros((E, C, d), x.dtype).at[e_row, s_row].add(u_row)

    buf = jax.vmap(row_scatter)(flat_e, safe_slot, upd)
    buf = constrain(buf, ("batch", "experts", None, "embed"))

    # Expert FFN (SwiGLU), batched over (row, expert).
    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    h = constrain(h, ("batch", "experts", None, "mlp"))
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"])
    # NOTE: no sharding constraint here — out_buf is a partial sum over the
    # model-sharded ffn dim, and gather/combine are linear, so the psum can
    # ride through to the (B,S,d) output: 12.5x fewer all-reduce bytes than
    # reducing the capacity-inflated buffer (§Perf climb #2, change 3).

    # Gather back and combine with gates (vmapped for the same reason).
    def row_gather(o_row, e_row, s_row):
        return o_row[e_row, s_row]

    gathered = jax.vmap(row_gather)(
        out_buf, flat_e, jnp.where(in_cap, slot, 0))              # (B,Tk,d)
    gathered = jnp.where(in_cap[..., None], gathered, 0)
    gathered = gathered.reshape(B, S, cfg.top_k, d)
    y = jnp.einsum("bskd,bsk->bsd", gathered.astype(F32),
                   gate_vals).astype(x.dtype)
    return constrain(y, ("batch", "seq", "embed"))


# ================================================================== facade
def moe(p, x, cfg: MoEConfig, dispatch: Optional[str] = None):
    """x: (B, S, d) -> (B, S, d).

    ``dispatch`` overrides ``cfg.dispatch`` (tests / benchmarks);
    production callers leave it None and get dropless.  ep parallelism on
    an active multi-device mesh takes the ragged all-to-all shard_map
    path; without one (single device, CPU smoke tests) the flat dropless
    layout already computes the identical padded-expert function (dead
    experts receive no rows), so the two agree exactly.
    """
    mode = dispatch if dispatch is not None else cfg.dispatch
    if mode == "dropless":
        mesh = _ep_mesh(cfg)
        if mesh is not None:
            return _moe_dropless_ep(p, x, cfg, mesh)
        return _moe_dropless(p, x, cfg)
    assert mode == "capacity", mode
    return _moe_capacity(p, x, cfg)


def moe_decode(p, x, cfg: MoEConfig, dispatch: Optional[str] = None):
    """Decode-time MoE: x is (B, 1, d), one new token per sequence.

    Not a separate code path: decode flows through ``moe`` and therefore
    ``route_tokens`` + the same grouped GEMM as prefill, which is the
    guarantee that ring-decode logits match prefill logits (the two compute
    the same mathematical function of each token's hidden state, and the
    assignment is bitwise-identical regardless of chunking)."""
    return moe(p, x, cfg, dispatch=dispatch)
