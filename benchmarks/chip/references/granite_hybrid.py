"""Plain reference of granite-4.0-H's decoder (Hugging Face's
``GraniteMoeHybridForCausalLM``): a stack of Mamba-2 mixers and GQA
attention layers without positions (NoPE), each mixer followed by routed
experts (softmax over the top-k router logits) beside one shared SwiGLU,
Granite's multipliers, a tied embedding.  Float32 throughout at the
highest matmul precision, straight jax.numpy, no cache, no kernels, no
batching; the Mamba recurrence runs token by token.  It imports nothing of
the program.

Equations, for a token stream (T,), d the width, m_e, m_r, m_a, m_l the
embedding, residual and attention multipliers and the logits scaling:

    x   = E[tokens] * m_e
    h   = rmsnorm(x) * ln1                               (eps from the file)
    y   = mamba(h) or attention(h)                       (the layer's kind)
    x   = x + m_r * y
    h2  = rmsnorm(x) * ln2
    x   = x + m_r * (sum_{e in topk(l), e held} g_e swiglu(h2; W_e)
                     + swiglu(h2; W_shared)),
          l = h2 W_router (all n_experts), g = softmax(l[topk(l)])
    logits = (rmsnorm(x) * final_ln) E^T / m_l

Mamba-2 (``GraniteMoeHybridMambaLayer``), heads of P lanes, N state
lanes, G groups (head h reads group h // (H/G)):

    [z, xBC, dt] = h W_in
    xBC  = silu(causal_conv(xBC) + conv_b)               (width W, depthwise)
    x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(a_log)
    s_t  = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t        (per head, (P, N))
    y_t  = s_t C_t + D x_t
    y    = rmsnorm_G(y * silu(z)) * norm;  out = y W_out

Attention: q = h Wq, k = h Wk, v = h Wv (GQA), softmax(q k^T m_a, causal)
v, out = o Wo; no rotary embedding.

Departures, each also the program's: only the experts this chip holds
(the first ``n_local_experts``, its expert-parallel share) are summed —
the router still picks over every expert and its gates are normalized
over all k picks, so a token whose picks lie elsewhere gets less than
its full expert output here, as on the chip; the final norm and the
tied head stay on this stage (the first of a pipeline) so that served
tokens can be checked.

Weights: ``layer_weights(l)`` gives index ``l`` of every stacked leaf
(keyed ``ln1/scale``, ``moe/router``, ``mamba/in_proj``, ``attn/wq``
...); a layer's norms and FFN are index l, its mixer the index of the
layer among those of its kind.  ``quantize`` runs the same pass with every
matmul weight rounded to float8 (e4m3, one scale per output channel): the
control that a lower-precision path would read as.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512        # query rows per attention block
PAD = 1024           # every sequence is padded to one multiple of this

# Matmul weights and the axes they contract over (the rest are output
# channels, one float8 scale each).
MATMULS = {"attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,),
           "attn/wo": (0, 1), "mamba/in_proj": (0,), "mamba/out_proj": (0,),
           "shared/w_gate": (0,), "shared/w_up": (0,), "shared/w_down": (0,),
           "moe/w_gate": (1,), "moe/w_up": (1,), "moe/w_down": (1,)}


def fp8(w, contract):
    """Round ``w`` to float8 e4m3 with one scale per output channel."""
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def attention(w, h, n_real, m: dict):
    """Causal NoPE GQA of the first ``n_real`` rows of h (T, d)."""
    T = h.shape[0]
    H, K = int(m["n_heads"]), int(m["kv_heads"])
    q = jnp.einsum("td,dhk->thk", h, w["attn/wq"])
    k = jnp.repeat(jnp.einsum("td,dhk->thk", h, w["attn/wk"]), H // K, 1)
    v = jnp.repeat(jnp.einsum("td,dhk->thk", h, w["attn/wv"]), H // K, 1)
    pos = jnp.arange(T)
    valid_k = pos < n_real
    scale = float(m["attention_multiplier"])

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        ok = (pos[None, :] <= qpos[:, None]) & valid_k[None, :]
        s = jnp.where(ok[None], s, -1e30)
        return jnp.einsum("hqs,shv->qhv", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    o = o.reshape(T, o.shape[2], o.shape[3])
    return jnp.einsum("thv,hvd->td", o, w["attn/wo"])


def mamba(w, h, m: dict):
    """The Mamba-2 mixer over h (T, d), its recurrence token by token."""
    T = h.shape[0]
    H, P = int(m["mamba_n_heads"]), int(m["mamba_d_head"])
    N, G = int(m["mamba_d_state"]), int(m["mamba_n_groups"])
    W = int(m["mamba_d_conv"])
    di = H * P
    zxd = h @ w["mamba/in_proj"]
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * G * N], zxd[:, -H:]
    cw = w["mamba/conv_w"]                                   # (W, C)
    xp = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(xp[i:i + T] * cw[i] for i in range(W))
    if "mamba/conv_b" in w:
        conv = conv + w["mamba/conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, 1)
    Cm = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])            # (T, H)
    A = -jnp.exp(w["mamba/a_log"])

    def step(s, t):
        xt, dtt, bt, ct = t
        s = (jnp.exp(dtt * A)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])  # (H,P,N)
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, Bm, Cm))
    y = (y + w["mamba/D"][None, :, None] * x).reshape(T, di)
    g = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + float(m["rms_norm_eps"]))
    g = g.reshape(T, di) * w["mamba/norm/scale"]
    return g @ w["mamba/out_proj"]


def ffn(w, h, m: dict):
    """Routed experts over all n_experts, the held ones summed, and the
    shared SwiGLU."""
    logits = h @ w["moe/router"]                             # (T, E)
    top, experts = jax.lax.top_k(logits, int(m["top_k"]))
    gates = jax.nn.softmax(top, axis=-1)
    E = logits.shape[-1]
    weight = jnp.zeros((h.shape[0], E), h.dtype).at[
        jnp.arange(h.shape[0])[:, None], experts].add(gates)

    def one(acc, e):
        y = swiglu(h, w["moe/w_gate"][e], w["moe/w_up"][e],
                   w["moe/w_down"][e])
        return acc + weight[:, e][:, None] * y, None

    held = w["moe/w_gate"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return out + swiglu(h, w["shared/w_gate"], w["shared/w_up"],
                        w["shared/w_down"])


def _layer(kind, w, x, n_real, m):
    eps, rm = float(m["rms_norm_eps"]), float(m["residual_multiplier"])
    h = rmsnorm(x, w["ln1/scale"], eps)
    y = (attention(w, h, n_real, m) if kind == "attention"
         else mamba(w, h, m))
    x = x + rm * y
    return x + rm * ffn(w, rmsnorm(x, w["ln2/scale"], eps), m)


def logits(model: dict, layer_weights: Callable[[int], Dict],
           top: Dict, sequences: Sequence[Sequence[int]],
           firsts: Sequence[int], quantize: bool = False) -> List[np.ndarray]:
    """Logits (n, vocab) at positions ``firsts[i] ..`` end of each
    sequence ``i``, for the sizes ``model`` (the configuration file's
    ``model``).  ``layer_weights(l)`` gives index l of every stacked leaf
    in float32; ``top`` the tied embedding (``tied_embed/tok``) and the
    final norm."""
    kinds = list(model["layer_types"])

    def q(w):
        if not quantize:
            return w
        return {n: fp8(a, MATMULS[n]) if n in MATMULS else a
                for n, a in w.items()}

    with jax.default_matmul_precision("highest"):
        step = {kind: jax.jit(lambda w, x, n, kind=kind: _layer(
                    kind, q(w), x, n, model))
                for kind in sorted(set(kinds))}
        emb = top["tied_embed/tok"]
        xs, ns = [], []
        # One padded length for all: one compiled program per layer kind.
        T = -(-max(len(s) for s in sequences) // PAD) * PAD
        for seq in sequences:
            toks = np.zeros((T,), np.int32)
            toks[:len(seq)] = seq
            xs.append(jnp.take(emb, jnp.asarray(toks), axis=0)
                      * float(model["embedding_multiplier"]))
            ns.append(len(seq))
        seen = {k: 0 for k in kinds}
        for l, kind in enumerate(kinds):
            j = seen[kind]
            seen[kind] += 1
            w = dict(layer_weights(l))
            if j != l:
                own = layer_weights(j)
                pre = "attn/" if kind == "attention" else "mamba/"
                w.update({k: v for k, v in own.items() if k.startswith(pre)})
                del own
            xs = [step[kind](w, x, jnp.int32(n)) for x, n in zip(xs, ns)]
            del w
        head = emb.T
        if quantize:
            head = fp8(head, (0,))
        scale = 1.0 / float(model["logits_scaling"])
        out = []
        for x, n, first in zip(xs, ns, firsts):
            h = rmsnorm(x[first:n], top["final_ln/scale"],
                        float(model["rms_norm_eps"]))
            out.append(np.asarray((h @ head) * scale))
        return out


def gaps(ref: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """Per row, the gap by which the picked token's reference logit lies
    below the reference's best: ``ref`` (n, vocab), ``picked`` (n,)."""
    return ref.max(axis=-1) - ref[np.arange(len(picked)), picked]
