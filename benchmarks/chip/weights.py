"""Random weights from the seed, made on the device, and the device facts
a run reports (compile events, peak memory).

The weights are the benchmark's input, not the program's: every leaf of
the model's parameter tree is drawn here, from the seed, in the dtype it is
served in.  Weights of layer ``l`` of a stacked leaf come from their own
key, so ``LayerWeights`` can draw one layer again, bit for bit, for the
reference after the program's state is gone.

Scales: a matrix leaf is drawn with standard deviation 1/sqrt(fan-in),
where the fan-in is the size the leaf contracts over (the model width for
the query, key, value, gate, up, router and head projections; heads x
head size for the attention output; the expert or MLP width for the down
projection); norms are ones and the embedding has standard deviation 0.02.
At these scales a deep random network is not chaotic, so bfloat16 rounding
stays near its own size through every layer.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
Tree = Any


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaf_std(path: str, shape: Tuple[int, ...]) -> Optional[float]:
    """Standard deviation of a leaf, from its name; None means ones."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return None
    if path.startswith("embed/"):
        return 0.02
    if name == "wo":                      # (.., H, dh, d)
        return 1.0 / math.sqrt(shape[-3] * shape[-2])
    if name in ("wq", "wk", "wv"):        # (.., d, heads, dh)
        return 1.0 / math.sqrt(shape[-3])
    return 1.0 / math.sqrt(shape[-2])     # (.., fan_in, fan_out)


def _seed_key(seed: int) -> jax.Array:
    """A key from any whole number, 64-bit seeds included."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(
        jax.random.PRNGKey(s & 0xFFFFFFFF), (s >> 32) & 0xFFFFFFFF)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, path: str, shape, dtype, stacked: bool):
    std = leaf_std(path, shape)
    if std is None:
        return jnp.ones(shape, dtype)
    if stacked:
        keys = jax.vmap(lambda l: jax.random.fold_in(key, l))(
            jnp.arange(shape[0]))
        x = jax.vmap(lambda k: jax.random.normal(k, shape[1:], jnp.float32)
                     )(keys)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
    return (x * std).astype(dtype)


def init_weights(abstract: Tree, seed: int) -> Tree:
    """Every leaf of ``abstract`` (a tree of ShapeDtypeStructs, the model's
    parameter layout) drawn from ``seed`` in one jitted call on the
    default device.  Leaves under ``layers/`` are stacked by layer."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [_path(p) for p, _ in leaves]

    def make(key):
        return [_draw(_leaf_key(key, p), p, a.shape, a.dtype,
                      p.startswith("layers/"))
                for p, (_, a) in zip(paths, leaves)]

    out = jax.block_until_ready(jax.jit(make)(_seed_key(seed)))
    return jax.tree_util.tree_unflatten(treedef, out)


class LayerWeights:
    """Layer ``l`` of every stacked leaf, as ``init_weights`` drew it, in
    float32: ``draw(seed, l)`` gives ``{"attn/wq": array, ...}`` keyed by
    the path under ``layers/``.  One compiled program serves every layer."""

    def __init__(self, abstract: Tree):
        leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
        picked = [(_path(p), a) for p, a in leaves
                  if _path(p).startswith("layers/")]

        def make(key, layer):
            out = {}
            for p, a in picked:
                std = leaf_std(p, a.shape)
                if std is None:
                    x = jnp.ones(a.shape[1:], jnp.float32)
                else:
                    k = jax.random.fold_in(_leaf_key(key, p), layer)
                    x = jax.random.normal(k, a.shape[1:], jnp.float32) * std
                out[p[len("layers/"):]] = x.astype(a.dtype).astype(
                    jnp.float32)
            return out

        self._make = jax.jit(make)

    def draw(self, seed: int, layer: int) -> Dict[str, Any]:
        return self._make(_seed_key(seed), jnp.int32(layer))


def top_weights(abstract: Tree, seed: int) -> Dict[str, Any]:
    """The unstacked leaves (embedding, final norm, head) in float32."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
    picked = [(_path(p), a) for p, a in leaves
              if not _path(p).startswith("layers/")]

    def make(key):
        return {p: _draw(_leaf_key(key, p), p, a.shape, a.dtype, False)
                .astype(jnp.float32) for p, a in picked}

    return jax.jit(make)(_seed_key(seed))


class CompileClock:
    """JAX's own compile events with their host-clock time, so a run can
    count those that fall inside its measured window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self, clock):
        self.clock = clock
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name in self.EVENTS:
            self.events.append((self.clock(), name, secs))

    def backend_seconds(self) -> float:
        return sum(s for _, n, s in self.events if n == self.EVENTS[0])

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.events if t0 <= t <= t1)


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")

