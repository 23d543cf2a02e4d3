"""Plain reference of the decoder the configurations run: dense (SwiGLU
MLP) or mixture-of-experts (softmax router, top-k experts with their gates
renormalized, SwiGLU experts), pre-norm RMSNorm blocks, grouped-query
attention with rotary positions, untied output head.  Float32 throughout
at the highest matmul precision, straight jax.numpy, no cache, no kernels,
no batching.  It imports nothing of the program.

Equations, for a token stream x (T, d):

    h   = rmsnorm(x) * ln1                 (eps 1e-6, in float32)
    q,k,v = h Wq, h Wk, h Wv; rotary on q, k (half-split, theta)
    a   = softmax(q k^T / sqrt(dh), causal) v, head h on kv head h // G
    x   = x + a Wo
    h2  = rmsnorm(x) * ln2
    x   = x + ffn(h2)
    logits = (rmsnorm(x) * final_ln) W_head

Weights come from ``weights.layer_weights`` one layer at a time, so only
one float32 layer is on the device at once.  ``quantize`` runs the same
pass with every matmul weight rounded to float8 (e4m3, one scale per output
channel): the control that a lower-precision path would read as.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
Q_BLOCK = 512        # query rows per attention block
PAD = 1024           # every sequence is padded to one multiple of this

# Matmul weights and the axes they contract over (the rest are output
# channels, one float8 scale each).
MATMULS = {"attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,),
           "attn/wo": (0, 1), "mlp/w_gate": (0,), "mlp/w_up": (0,),
           "mlp/w_down": (0,), "moe/w_gate": (1,), "moe/w_up": (1,),
           "moe/w_down": (1,), "head/w": (0,)}


def fp8(w, contract):
    """Round ``w`` to float8 e4m3 with one scale per output channel."""
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def rope(x, pos, theta):
    """x: (T, heads, dh); pos: (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, x, n_real, theta):
    """Causal grouped-query attention of the first ``n_real`` rows of x."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = rope(jnp.einsum("td,dhk->thk", x, w["attn/wq"]), pos, theta)
    k = rope(jnp.einsum("td,dhk->thk", x, w["attn/wk"]), pos, theta)
    v = jnp.einsum("td,dhk->thk", x, w["attn/wv"])
    H, K, dh = q.shape[1], k.shape[1], q.shape[2]
    G = H // K
    qg = q.reshape(T, K, G, dh)
    valid_k = pos < n_real

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / np.sqrt(dh)
        ok = (pos[None, :] <= qpos[:, None]) & valid_k[None, :]
        s = jnp.where(ok[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    o = o.reshape(T, H, dh)
    return jnp.einsum("thk,hkd->td", o, w["attn/wo"])


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def ffn(w, h, top_k):
    if "mlp/w_gate" in w:
        return swiglu(h, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"])
    probs = jax.nn.softmax(h @ w["moe/router"], axis=-1)       # (T, E)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    E = probs.shape[-1]
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], experts].add(gates)  # (T, E)

    def one(acc, e):
        y = swiglu(h, w["moe/w_gate"][e], w["moe/w_up"][e],
                   w["moe/w_down"][e])
        return acc + weight[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return out


def _layer(w, x, n_real, theta, top_k):
    x = x + attention(w, rmsnorm(x, w["ln1/scale"]), n_real, theta)
    return x + ffn(w, rmsnorm(x, w["ln2/scale"]), top_k)


def logits(model: dict, layer_weights: Callable[[int], Dict],
           top: Dict, sequences: Sequence[Sequence[int]],
           firsts: Sequence[int], quantize: bool = False) -> List[np.ndarray]:
    """Logits (n, vocab) at positions ``firsts[i] ..`` end of each
    sequence ``i``, for the sizes ``model`` (the configuration file's
    ``model``).  ``layer_weights(l)`` gives layer l's weights in
    float32 keyed ``attn/wq`` ...; ``top`` the embedding, final norm and
    head (``embed/tok``, ``final_ln/scale``, ``head/w``)."""
    theta = float(model["rope_theta"])
    top_k = int(model.get("top_k", 0))

    def q(w):
        if not quantize:
            return w
        return {n: fp8(a, MATMULS[n]) if n in MATMULS else a
                for n, a in w.items()}

    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda w, x, n: _layer(q(w), x, n, theta, top_k))
        emb = top["embed/tok"]
        xs, ns = [], []
        # One padded length for all: one compiled layer program.
        T = -(-max(len(s) for s in sequences) // PAD) * PAD
        for seq in sequences:
            n = len(seq)
            toks = np.zeros((T,), np.int32)
            toks[:n] = seq
            xs.append(jnp.take(emb, jnp.asarray(toks), axis=0))
            ns.append(n)
        for l in range(int(model["n_layers"])):
            w = layer_weights(l)
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
