"""Mamba-2 mixer (state-space duality, arXiv:2405.21060) — pure JAX.

The block as published (``mamba_ssm``'s ``Mamba2``, Hugging Face's
``Mamba2Mixer`` / ``GraniteMoeHybridMambaLayer``):

    [z, xBC, dt] = u W_in                     (one input projection)
    xBC = silu(causal_conv(xBC) + conv_bias)  (depthwise, width W, over x,
                                               B and C together)
    x, B, C = split(xBC)                      (d_inner, G*N, G*N)
    dt = softplus(dt + dt_bias)               (no clip: the time-step limit
                                               is (0, inf))
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = C_t . h_t + D x_t
    y = rmsnorm_G(y * silu(z)) * norm         (over d_inner / G lanes each,
                                               eps from the config)
    out = y W_out

Heads are H = d_inner / P of width P; head h reads B/C group h // (H/G).
Training and prefill use the chunked SSD algorithm (quadratic within a
chunk, a scan over chunk states); decode is the one-token recurrence
(``ssm_state_step``; ``kernels/ssm_decode.py`` is its Pallas form).

The recurrent state of a sequence is (conv_state, ssm_state): the last
W-1 inputs of the conv, (B, W-1, conv_dim), and h laid out (B, N, H, P)
— state lane n of head h's P values, so a flat (N, H*P) row is lane-dense
for the decode kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArrayDef

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_inner: int          # expand * d_model
    head_dim: int = 64    # P
    state_dim: int = 64   # N
    n_groups: int = 1     # G: B/C groups
    conv_width: int = 4
    conv_bias: bool = True
    chunk: int = 256
    eps: float = 1e-6     # the gated norm's

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_dim

    @property
    def proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.n_heads


def ssm_defs(cfg: SSMConfig):
    d, di, H = cfg.d_model, cfg.d_inner, cfg.n_heads
    out = {
        "in_proj": ArrayDef((d, cfg.proj_dim), ("embed", None)),
        "conv_w": ArrayDef((cfg.conv_width, cfg.conv_dim), ("conv", None),
                           init="normal", scale=0.5),
        "dt_bias": ArrayDef((H,), ("ssm_heads",), dtype=F32, init="zeros"),
        "a_log": ArrayDef((H,), ("ssm_heads",), dtype=F32, init="zeros"),
        "D": ArrayDef((H,), ("ssm_heads",), dtype=F32, init="ones"),
        "norm": {"scale": ArrayDef((di,), ("d_inner",), init="ones")},
        "out_proj": ArrayDef((di, d), ("d_inner", "embed")),
    }
    if cfg.conv_bias:
        out["conv_b"] = ArrayDef((cfg.conv_dim,), (None,), init="zeros")
    return out


# ------------------------------------------------------------ the pieces
def ssm_project(p, u, cfg: SSMConfig):
    """u (..., d) -> z (..., d_inner), xBC (..., conv_dim) before the conv,
    and dt (..., H) float32 after softplus(dt + dt_bias)."""
    zxd = jnp.einsum("...d,de->...e", u, p["in_proj"])
    di, C = cfg.d_inner, cfg.conv_dim
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + C], zxd[..., di + C:]
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    return z, xbc, dt


def conv_window(p, window, dtype):
    """The conv's output for the last row of each window (..., W, C):
    silu(sum_i w_i window_i + b), in ``dtype``."""
    y = jnp.einsum("...wc,wc->...c", window.astype(F32),
                   p["conv_w"].astype(F32))
    if "conv_b" in p:
        y = y + p["conv_b"].astype(F32)
    return jax.nn.silu(y).astype(dtype)


def split_xbc(xbc, cfg: SSMConfig):
    """(..., conv_dim) -> x (..., H, P), B (..., G, N), C (..., G, N)."""
    di, G, N = cfg.d_inner, cfg.n_groups, cfg.state_dim
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(lead + (cfg.n_heads, cfg.head_dim))
    Bm = xbc[..., di:di + G * N].reshape(lead + (G, N))
    Cm = xbc[..., di + G * N:].reshape(lead + (G, N))
    return x, Bm, Cm


def gated_out(p, y, z, cfg: SSMConfig, dtype):
    """rmsnorm over each group's d_inner/G lanes of y * silu(z), times the
    norm's scale, then the output projection.  y (..., d_inner) f32."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    lead = g.shape[:-1]
    gg = g.reshape(lead + (cfg.n_groups, cfg.d_inner // cfg.n_groups))
    var = jnp.mean(jnp.square(gg), axis=-1, keepdims=True)
    g = (gg * jax.lax.rsqrt(var + cfg.eps)).reshape(g.shape)
    g = (g * p["norm"]["scale"].astype(F32)).astype(dtype)
    return jnp.einsum("...e,ed->...d", g, p["out_proj"])


def heads_of(m, cfg: SSMConfig):
    """A (..., G, N) group array repeated to (..., H, N), head by head."""
    return jnp.repeat(m, cfg.n_heads // cfg.n_groups, axis=-2)


def ssm_state_step(state, x, dt, A, Bm, Cm, D, cfg: SSMConfig):
    """One token of the recurrence.  state (B, N, H, P) f32; x (B, H, P);
    dt (B, H) f32; A, D (H,); Bm, Cm (B, G, N).  Returns (y (B, H, P) f32,
    the new state)."""
    xf = x.astype(F32)
    Bh, Ch = heads_of(Bm.astype(F32), cfg), heads_of(Cm.astype(F32), cfg)
    decay = jnp.exp(dt * A)                                  # (B, H)
    new = (decay[:, None, :, None] * state
           + jnp.einsum("bhn,bhp->bnhp", Bh, xf * dt[..., None]))
    y = jnp.einsum("bhn,bnhp->bhp", Ch, new) + xf * D[None, :, None]
    return y, new


def ssd_chunked(x, dt, A, Bm, Cm, cfg: SSMConfig,
                init_state=None) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x: (B,S,H,P) after the conv; dt: (B,S,H) f32 (0 leaves the state as
    it is); A: (H,) f32 negative; Bm/Cm: (B,S,G,N); init_state (B,N,H,P)
    f32 or None (zeros).  Returns y (B,S,H,P) f32 without the D term, and
    the final state (B,N,H,P).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    Hg = H // G
    Q = min(cfg.chunk, S)
    pad = -S % Q
    if pad:        # rows with dt = 0 leave the state as it is
        def padded(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        y, final = ssd_chunked(padded(x), padded(dt), A, padded(Bm),
                               padded(Cm), cfg, init_state)
        return y[:, :S], final
    nc = S // Q

    # One scan over chunks: each step computes the intra-chunk causal block
    # (quadratic in Q only) plus the carried-state contribution, then updates
    # the running state.  Live intermediates stay O(B*Q*Q*H) for one chunk.
    def chunks(a, tail):
        return a.reshape((Bsz, nc, Q) + tail).swapaxes(0, 1)

    xs = chunks(x, (G, Hg, P))
    dts = chunks(dt, (G, Hg))
    Bs = chunks(Bm.astype(F32), (G, N))
    Cs = chunks(Cm.astype(F32), (G, N))
    Ag = A.reshape(G, Hg)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    if init_state is None:
        init_state = jnp.zeros((Bsz, N, H, P), F32)
    s0 = init_state.reshape(Bsz, N, G, Hg, P)

    @jax.checkpoint
    def step(s_prev, inp):
        xc, dtc, Bc, Cc = inp       # (B,Q,G,Hg,P) (B,Q,G,Hg) (B,Q,G,N) x2
        a = dtc * Ag                        # log decay, negative
        a_cum = jnp.cumsum(a, axis=1)
        a_tot = a_cum[:, -1]                # (B,G,Hg)
        xdt = xc.astype(F32) * dtc[..., None]
        # decay(i,j) = exp(a_cum[i]-a_cum[j]) masked to j<=i.  Mask the
        # exponent (not the result): exp of the unmasked upper triangle
        # overflows and would poison gradients through the where.
        diff = a_cum[:, :, None] - a_cum[:, None, :]       # (B,Q,Q,G,Hg)
        diff = jnp.where(causal[None, :, :, None, None], diff, -1e30)
        L = jnp.exp(diff)
        scores = jnp.einsum("bign,bjgn->bijg", Cc, Bc)     # (B,Q,Q,G)
        y_diag = jnp.einsum("bijg,bijgh,bjghp->bighp", scores, L, xdt)
        # carried state contribution
        y_off = jnp.einsum("bign,bigh,bnghp->bighp", Cc, jnp.exp(a_cum),
                           s_prev)
        # state update
        decay_to_end = jnp.exp(a_tot[:, None] - a_cum)     # (B,Q,G,Hg)
        s_chunk = jnp.einsum("bjgn,bjgh,bjghp->bnghp", Bc, decay_to_end, xdt)
        s_new = s_chunk + jnp.exp(a_tot)[:, None, :, :, None] * s_prev
        return s_new, y_diag + y_off

    final, ys = jax.lax.scan(step, s0, (xs, dts, Bs, Cs))
    y = ys.swapaxes(0, 1).reshape(Bsz, S, H, P)
    return y, final.reshape(Bsz, N, H, P)


def ssm_forward(p, u, cfg: SSMConfig, conv_state=None, ssm_state=None,
                n_real=None):
    """The mixer over a run of tokens.  u: (B,S,d).  ``conv_state`` and
    ``ssm_state`` continue a sequence (zeros when None).  Rows at or past
    ``n_real`` (a traced count; None: all live) are padding: they leave
    both states exactly as the last live row left them.  Returns (out
    (B,S,d), conv_state (B,W-1,conv_dim), ssm_state (B,N,H,P) f32)."""
    B, S, _ = u.shape
    W = cfg.conv_width
    z, xbc, dt = ssm_project(p, u, cfg)
    if n_real is not None:
        dt = jnp.where((jnp.arange(S) < n_real)[None, :, None], dt, 0.0)
    if conv_state is None:
        conv_state = jnp.zeros((B, W - 1, cfg.conv_dim), xbc.dtype)
    full = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    windows = jnp.stack([full[:, i:i + S] for i in range(W)], axis=2)
    xbc = conv_window(p, windows, u.dtype)                   # (B,S,C)
    x, Bm, Cm = split_xbc(xbc, cfg)
    A = -jnp.exp(p["a_log"])
    y, final = ssd_chunked(x, dt, A, Bm, Cm, cfg, init_state=ssm_state)
    y = y + x.astype(F32) * p["D"][None, None, :, None]
    out = gated_out(p, y.reshape(B, S, cfg.d_inner), z, cfg, u.dtype)
    end = S if n_real is None else n_real
    new_conv = jax.lax.dynamic_slice_in_dim(full, end, W - 1, axis=1)
    return out, new_conv, final


def ssm_decode(p, u, conv_state, ssm_state, cfg: SSMConfig):
    """One-token decode.  u: (B,1,d); conv_state: (B, W-1, conv_dim);
    ssm_state: (B,N,H,P) f32.  Returns (out (B,1,d), conv_state,
    ssm_state)."""
    z, xbc, dt = ssm_project(p, u[:, 0], cfg)
    window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc[:, None]],
                             axis=1)
    x, Bm, Cm = split_xbc(conv_window(p, window, u.dtype), cfg)
    y, new_state = ssm_state_step(ssm_state, x, dt, -jnp.exp(p["a_log"]),
                                  Bm, Cm, p["D"], cfg)
    out = gated_out(p, y.reshape(y.shape[0], cfg.d_inner), z, cfg, u.dtype)
    return out[:, None], window[:, 1:], new_state
