"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434, Sec. 2.1
multi-head latent attention, Sec. 2.2 DeepSeekMoE): leading dense SwiGLU
layers, then layers of routed experts (softmax router, top-k) beside
always-on shared experts; pre-norm RMSNorm blocks, YaRN rope, untied
output head.  Float32 throughout at the highest matmul precision, straight
jax.numpy, no cache, no kernels, no batching, the latent attention in its
plain (un-absorbed) form.  It imports nothing of the program.

Equations, for a token stream x (T, d), C = kv_lora_rank, dn = qk_nope,
dr = qk_rope, H heads:

    h   = rmsnorm(x) * ln1                           (eps 1e-6, float32)
    q   = h Wq  (T, H, dn + dr) = [q_nope, q_pe];  q_pe = rope(q_pe)
    kv  = h Wkv_a (T, C + dr) = [c, k_pe]
    c   = rmsnorm(c) * kv_norm;  k_pe = rope(k_pe), one head for all
    k_h = [c Wk_h, k_pe];  v_h = c Wv_h          (Wk, Wv: W_kv_b's halves)
    a_h = softmax(q_h k_h^T * s, causal) v_h,  s = (dn + dr)^-1/2 * m^2,
          m = 0.1 * mscale_all_dim * ln(factor) + 1
    x   = x + sum_h a_h Wo_h
    h2  = rmsnorm(x) * ln2
    x   = x + swiglu(h2; W_mlp)                               (dense layer)
    x   = x + r * sum_{e in topk(p)} p_e swiglu(h2; W_e)
            + swiglu(h2; W_shared),   p = softmax(h2 W_router)  (MoE layer)
    logits = (rmsnorm(x) * final_ln) W_head

Rope (YaRN, DeepSeek's ``DeepseekV2YarnRotaryEmbedding``): over dr lanes
with base theta, inverse frequency f_i = theta^(-2i/dr) for pairs that turn
more than beta_fast times over the original context, f_i / factor for those
under beta_slow turns, linearly blended between (the ramp of low = floor
(d(beta_fast)), high = ceil(d(beta_slow)), d(n) = dr ln(orig / (2 pi n)) /
(2 ln theta)); cos and sin scaled by m(mscale) / m(mscale_all_dim).

Departures, each also the program's: rope pairs lanes half-split (lane i
with lane i + dr/2) where DeepSeek's checkpoint pairs interleaved lanes —
equal under a fixed permutation of the rope columns of Wq and Wkv_a, which
seeded random weights do not distinguish; the gates are not renormalized
when ``norm_topk_prob`` is false (DeepSeek-V2-Lite), r is
``routed_scaling_factor``.

Layers: the leading dense layers' weights are in ``top`` (``dense_layers/
...``, stacked), the MoE layers' come from ``layer_weights(l)`` one at a
time, so only one float32 MoE layer is on the device at once.
``quantize`` runs the same pass with every matmul weight rounded to
float8 (e4m3, one scale per output channel): the control that a
lower-precision path would read as.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
Q_BLOCK = 512        # query rows per attention block
PAD = 1024           # every sequence is padded to one multiple of this

# Matmul weights and the axes they contract over (the rest are output
# channels, one float8 scale each).
MATMULS = {"attn/wq": (0,), "attn/wkv_a": (0,), "attn/wk": (0,),
           "attn/wv": (0,), "attn/wo": (0, 1), "mlp/w_gate": (0,),
           "mlp/w_up": (0,), "mlp/w_down": (0,), "shared/w_gate": (0,),
           "shared/w_up": (0,), "shared/w_down": (0,), "moe/w_gate": (1,),
           "moe/w_up": (1,), "moe/w_down": (1,), "head/w": (0,)}


def fp8(w, contract):
    """Round ``w`` to float8 e4m3 with one scale per output channel."""
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: dict) -> np.ndarray:
    def turns_dim(n):
        return (dim * math.log(y["original_max_position"] / (2 * math.pi * n))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(y["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    return (plain / y["factor"] * ramp + plain * (1.0 - ramp)).astype(
        np.float32)


def rope(x, pos, inv_freq, mscale):
    """x: (T, heads, dr); pos: (T,).  Half-split pairs."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * mscale)[:, None]
    sin = (jnp.sin(ang) * mscale)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, x, n_real, m: dict):
    """Causal latent attention of the first ``n_real`` rows of x, in the
    plain form: every head's key and value made from the latent."""
    T = x.shape[0]
    C, dn = int(m["kv_lora_rank"]), int(m["qk_nope_head_dim"])
    dr = int(m["qk_rope_head_dim"])
    y = m["yarn"]
    inv = jnp.asarray(yarn_inv_freq(dr, float(m["rope_theta"]), y))
    rope_ms = (yarn_mscale(y["factor"], y["mscale"])
               / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ms = yarn_mscale(y["factor"], y["mscale_all_dim"])
    scale = ms * ms / math.sqrt(dn + dr)
    pos = jnp.arange(T)
    q = jnp.einsum("td,dhk->thk", x, w["attn/wq"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, inv, rope_ms)],
                        -1)
    kv = x @ w["attn/wkv_a"]
    c = rmsnorm(kv[:, :C], w["attn/kv_norm/scale"])
    k_pe = rope(kv[:, None, C:], pos, inv, rope_ms)            # (T, 1, dr)
    k_nope = jnp.einsum("tc,chn->thn", c, w["attn/wk"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + (dr,))], -1)
    v = jnp.einsum("tc,chv->thv", c, w["attn/wv"])
    valid_k = pos < n_real

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        ok = (pos[None, :] <= qpos[:, None]) & valid_k[None, :]
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqs,shv->qhv", p, v)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    o = o.reshape(T, o.shape[2], o.shape[3])
    return jnp.einsum("thv,hvd->td", o, w["attn/wo"])


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def ffn(w, h, m: dict):
    if "mlp/w_gate" in w:
        return swiglu(h, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"])
    probs = jax.nn.softmax(h @ w["moe/router"], axis=-1)       # (T, E)
    gates, experts = jax.lax.top_k(probs, int(m["top_k"]))
    if m["norm_topk_prob"]:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates * float(m["routed_scaling_factor"])
    E = probs.shape[-1]
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], experts].add(gates)  # (T, E)

    def one(acc, e):
        y = swiglu(h, w["moe/w_gate"][e], w["moe/w_up"][e],
                   w["moe/w_down"][e])
        return acc + weight[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return out + swiglu(h, w["shared/w_gate"], w["shared/w_up"],
                        w["shared/w_down"])


def _layer(w, x, n_real, m):
    x = x + attention(w, rmsnorm(x, w["ln1/scale"]), n_real, m)
    return x + ffn(w, rmsnorm(x, w["ln2/scale"]), m)


def logits(model: dict, layer_weights: Callable[[int], Dict],
           top: Dict, sequences: Sequence[Sequence[int]],
           firsts: Sequence[int], quantize: bool = False) -> List[np.ndarray]:
    """Logits (n, vocab) at positions ``firsts[i] ..`` end of each
    sequence ``i``, for the sizes ``model`` (the configuration file's
    ``model``).  ``layer_weights(l)`` gives MoE layer l's weights (the
    model's layer first_dense_layers + l) in float32 keyed ``attn/wq`` ...;
    ``top`` the embedding, final norm, head and the leading dense layers
    (``dense_layers/attn/wq`` ..., stacked)."""
    n_dense = int(model["first_dense_layers"])

    def q(w):
        if not quantize:
            return w
        return {n: fp8(a, MATMULS[n]) if n in MATMULS else a
                for n, a in w.items()}

    def dense(i):
        pre = "dense_layers/"
        return {k[len(pre):]: v[i] for k, v in top.items()
                if k.startswith(pre)}

    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda w, x, n: _layer(q(w), x, n, model))
        emb = top["embed/tok"]
        xs, ns = [], []
        # One padded length for all: one compiled program per layer kind.
        T = -(-max(len(s) for s in sequences) // PAD) * PAD
        for seq in sequences:
            n = len(seq)
            toks = np.zeros((T,), np.int32)
            toks[:n] = seq
            xs.append(jnp.take(emb, jnp.asarray(toks), axis=0))
            ns.append(n)
        for l in range(int(model["n_layers"])):
            w = dense(l) if l < n_dense else layer_weights(l - n_dense)
            xs = [layer(w, x, jnp.int32(n)) for x, n in zip(xs, ns)]
            del w
        head = q({"head/w": top["head/w"]})["head/w"]
        out = []
        for x, n, first in zip(xs, ns, firsts):
            h = rmsnorm(x[first:n], top["final_ln/scale"])
            out.append(np.asarray(h @ head))
        return out


def gaps(ref: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """Per row, the gap by which the picked token's reference logit lies
    below the reference's best: ``ref`` (n, vocab), ``picked`` (n,)."""
    return ref.max(axis=-1) - ref[np.arange(len(picked)), picked]
