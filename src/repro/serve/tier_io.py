"""Row moves between HBM arrays and pinned host memory.

The one mechanism behind both host tiers of the serving stack: KV pages
(serve/kvcache.py) and MoE expert blocks (serve/expert_store.py).

JAX moves bytes between memory kinds only as whole arrays:
``jax.device_put`` onto a sharding that names the kind.  Neither the CPU
backend nor the TPU compiler runs a gather or scatter whose operand sits in
``pinned_host`` memory (eagerly JAX refuses mixed memory spaces; jitted, the
host-offload annotations it would need are missing on the CPU and refused
for gathers on the TPU).  And each pinned_host array costs a fixed price to
create and fill on the TPU, far above its bytes at page sizes.  So the host
tier (``HostTier``) holds rows in *slabs*: every park is one pinned_host
array per pool array holding all the rows it parks, and a row is addressed
by the slot number the caller gives it.

* device -> host (``gather_rows`` + ``HostTier.park``): one gather per
  group of rows on the device, then ONE batched ``jax.device_put`` per
  pool array for every group;
* host -> device (``HostTier.fetch`` + ``copy_rows``): ONE batched
  ``jax.device_put`` per pool array of the slabs holding the rows, then one
  small in-place update per row of the device array (donated, so it is
  never copied whole).

A slab's host memory is freed when its last row is released.  Fetching a
row moves its whole slab, so callers park rows that travel together (one
request's pages, one layer's experts) in one slab.  ``fetch`` returns the
staged arrays as ``jax.device_put`` does, and its callers install them at
once (the KV tier) or several dispatches later (the expert store's lead).
Whether that call returns before the bytes land on a TPU v5e, and whether
the copy runs beside programs, is not measured yet (PERF.md §7).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HOST_KIND = "pinned_host"
DEVICE_KIND = "device"

Arrays = Tuple[jax.Array, ...]       # one array per pool array (K, V, ...)


def tier_shardings(device) -> Tuple[jax.sharding.Sharding,
                                    jax.sharding.Sharding]:
    """(HBM, pinned host) shardings of one device.  A backend without the
    ``pinned_host`` kind fails at the first transfer, by name."""
    return (jax.sharding.SingleDeviceSharding(device, memory_kind=DEVICE_KIND),
            jax.sharding.SingleDeviceSharding(device, memory_kind=HOST_KIND))


@functools.partial(jax.jit, static_argnames=("axis",))
def _gather(arrays, idx, axis: int):
    return tuple(jnp.take(a, idx, axis=axis) for a in arrays)


@functools.partial(jax.jit, static_argnames=("axis",), donate_argnums=0)
def _copy_row(dst, i, src, j, axis: int):
    return tuple(
        jax.lax.dynamic_update_index_in_dim(
            d, jax.lax.dynamic_index_in_dim(s, j, axis, keepdims=False),
            i, axis)
        for d, s in zip(dst, src))


def gather_rows(arrays: Sequence[jax.Array], idx: Sequence[int],
                axis: int) -> Arrays:
    """Rows ``idx`` of device arrays, stacked along ``axis``.  The count is
    padded to a power of two (repeats of the last row), so one executable
    serves every batch of a size class; row n of the result is ``idx[n]``
    for n < len(idx)."""
    idx = [int(i) for i in idx]
    pad = (1 << (len(idx) - 1).bit_length()) - len(idx)
    return _gather(tuple(arrays), jnp.asarray(idx + idx[-1:] * pad,
                                              jnp.int32), axis=axis)


def copy_rows(dst: Sequence[jax.Array], dst_idx: Sequence[int],
              src: Sequence[Arrays], locs: Sequence[Tuple[int, int]],
              axis: int) -> Arrays:
    """``dst[.., dst_idx[n], ..] = src[p][.., j, ..]`` for ``(p, j) =
    locs[n]``, in place: ``dst`` is donated, use the returned arrays."""
    dst = tuple(dst)
    for i, (p, j) in zip(dst_idx, locs):
        dst = _copy_row(dst, int(i), tuple(src[p]), int(j), axis=axis)
    return dst


class HostTier:
    """Rows parked in pinned host memory, one slab per park (see the module
    docstring).  ``axis`` is the row axis of the parked arrays."""

    def __init__(self, device, axis: int):
        self.axis = axis
        self.device_sharding, self.host_sharding = tier_shardings(device)
        self._slabs: Dict[int, Tuple[Arrays, Set[int]]] = {}
        self._where: Dict[int, Tuple[int, int]] = {}   # slot -> (slab, row)
        self._next_slab = 0

    def __contains__(self, slot: int) -> bool:
        return slot in self._where

    def park(self, groups: Sequence[Tuple[Sequence[int], Sequence]]
             ) -> List[Arrays]:
        """Park each group ``(slots, rows)`` as one slab: ``rows`` holds one
        array per pool array (device or numpy) whose row n along ``axis``
        belongs to ``slots[n]`` (rows past ``len(slots)`` are padding).
        ONE batched transfer per pool array for all the groups; returns the
        new slabs."""
        if not groups:
            return []
        moved = [jax.device_put([rows[a] for _, rows in groups],
                                self.host_sharding)
                 for a in range(len(groups[0][1]))]
        slabs = [tuple(m[g] for m in moved) for g in range(len(groups))]
        for slab, (slots, _) in zip(slabs, groups):
            sid = self._next_slab
            self._next_slab += 1
            self._slabs[sid] = (slab, set(slots))
            for j, s in enumerate(slots):
                self._where[s] = (sid, j)
        return slabs

    def _locate(self, slots: Sequence[int]):
        order: Dict[int, int] = {}
        locs = []
        for s in slots:
            sid, j = self._where[s]
            locs.append((order.setdefault(sid, len(order)), j))
        return list(order), locs

    def fetch(self, slots: Sequence[int]
              ) -> Tuple[List[Arrays], List[Tuple[int, int]]]:
        """Bring the slabs holding ``slots`` to the device: ONE batched
        transfer per pool array.  Returns the staged slabs and, per slot,
        its (slab position, row) in them — ``copy_rows``' ``src, locs``."""
        sids, locs = self._locate(slots)
        if not sids:
            return [], []
        moved = [jax.device_put([self._slabs[sid][0][a] for sid in sids],
                                self.device_sharding)
                 for a in range(len(self._slabs[sids[0]][0]))]
        return [tuple(m[p] for m in moved) for p in range(len(sids))], locs

    def read(self, slots: Sequence[int]) -> Tuple[List[np.ndarray], ...]:
        """The rows of ``slots`` as numpy, one list per pool array."""
        sids, locs = self._locate(slots)
        slabs = jax.device_get([self._slabs[sid][0] for sid in sids])
        n_arrays = len(slabs[0]) if slabs else 0
        return tuple([np.take(slabs[p][a], j, axis=self.axis)
                      for p, j in locs] for a in range(n_arrays))

    def release(self, slots: Sequence[int]) -> None:
        """Forget ``slots``; a slab's memory goes with its last row."""
        for s in slots:
            sid, _ = self._where.pop(s)
            live = self._slabs[sid][1]
            live.discard(s)
            if not live:
                del self._slabs[sid]

    def memory_kinds(self) -> Set[str]:
        return {a.sharding.memory_kind
                for arrays, _ in self._slabs.values() for a in arrays}
