"""Per-twin ODE state carried between streaming requests, with host paging.

A streaming twin population is resident state, not request payload: each
physical asset owns a carried ``(y, global step)`` pair that every new
sensor window advances.  The population can vastly exceed what should sit
in device memory next to the serving kernels, so the store is two-level:

  * **hot slab** — one device array of ``hot_capacity`` rows.  Twins that
    are about to be batched are promoted here; the batch assembler gathers
    their rows with one indexed read and scatters results back with one
    indexed write (no per-twin device round-trips on the serving path).
  * **cold pages** — plain NumPy host arrays, one per twin.  Eviction is
    LRU over the hot slot order: promoting into a full slab pages the
    least-recently-used resident twins' rows back to host FIRST (one
    device->host read per fetch), then reuses their slots — state is
    never dropped, only moved (the invariant ``tests/traffic.py`` checks
    after every stress schedule).

Metadata (global step index, per-twin drive parameters) always lives on
the host: steps parameterise the canonical float64 time grid
(:func:`repro.kernels.ops.window_times`) and must stay concrete Python
integers for the determinism contract to hold.

The store is deliberately synchronous and single-writer — the streaming
server (`repro.launch.fleet_serving.StreamingFleetServer`) owns it and
serialises access through its batch loop.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import chaos

TwinId = Any


@dataclasses.dataclass
class StoreStats:
    """Paging counters (one per store)."""
    registered: int = 0
    hot_hits: int = 0        # fetches served from the hot slab
    page_ins: int = 0        # cold -> hot promotions
    evictions: int = 0       # hot -> cold LRU pagings (rows)
    evict_reads: int = 0     # device->host reads that paged them out:
                             # one per fetch that evicts
    commits: int = 0         # state writes after served batches

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class TwinStateStore:
    """Two-level (device-hot / host-cold) store of per-twin ODE state.

    ``hot_capacity`` bounds the device-resident population; everything
    beyond it pages to host NumPy arrays with LRU eviction.  ``fetch``
    promotes + gathers, ``commit`` scatters back; both operate on id
    lists so the serving loop touches the device once per batch.
    """

    def __init__(self, state_dim: int, hot_capacity: int, *,
                 dtype=jnp.float32):
        if hot_capacity < 1:
            raise ValueError(
                f"TwinStateStore: hot_capacity must be >= 1, got "
                f"{hot_capacity}")
        self.state_dim = int(state_dim)
        self.hot_capacity = int(hot_capacity)
        self._hot = jnp.zeros((self.hot_capacity, self.state_dim), dtype)
        self._free: list[int] = list(range(self.hot_capacity))[::-1]
        self._slot_of: "OrderedDict[TwinId, int]" = OrderedDict()  # LRU order
        self._cold: dict[TwinId, np.ndarray] = {}
        self._step: dict[TwinId, int] = {}
        self._theta: dict[TwinId, Optional[np.ndarray]] = {}
        self.stats = StoreStats()

    # -- population --------------------------------------------------------
    def __contains__(self, twin_id: TwinId) -> bool:
        return twin_id in self._step

    def __len__(self) -> int:
        return len(self._step)

    @property
    def hot_ids(self) -> list:
        """Device-resident twins, least recently used first."""
        return list(self._slot_of)

    def register(self, twin_id: TwinId, y0, *, theta=None,
                 step: int = 0) -> None:
        """Admit a new twin with its initial state (host-side — nothing
        touches the device until the twin is first batched)."""
        if twin_id in self:
            raise ValueError(f"twin {twin_id!r} already registered")
        y0 = np.asarray(y0, np.float32)
        if y0.shape != (self.state_dim,):
            raise ValueError(
                f"twin {twin_id!r}: y0 shape {y0.shape} != "
                f"({self.state_dim},)")
        if not np.isfinite(y0).all():
            raise ValueError(
                f"twin {twin_id!r}: y0 contains non-finite values")
        self._cold[twin_id] = y0
        self._step[twin_id] = int(step)
        self._theta[twin_id] = (None if theta is None
                                else np.asarray(theta, np.float32))
        self.stats.registered += 1

    # -- paging ------------------------------------------------------------
    def _evict_lru(self, pinned: set, evicted: list) -> int:
        """Take the least-recently-used unpinned hot twin's slot, and
        record ``(twin_id, slot)`` on ``evicted`` for
        :meth:`_page_out`."""
        chaos.kill_point("store:evict")
        for twin_id in self._slot_of:          # iteration order = LRU
            if twin_id not in pinned:
                slot = self._slot_of.pop(twin_id)
                evicted.append((twin_id, slot))
                return slot
        raise RuntimeError(
            f"TwinStateStore: cannot evict — all {self.hot_capacity} hot "
            f"slots are pinned by the current batch (batch larger than "
            f"hot_capacity?)")

    def _page_out(self, evicted: list) -> None:
        """Copy the evicted twins' device rows to host pages in one
        gather and one device->host read.  The index vector is padded to
        the next power of two (repeating the last slot), so eviction
        counts share at most ceil(log2(hot_capacity)) + 1 compiled
        gathers."""
        k = len(evicted)
        slots = [s for _, s in evicted]
        slots += slots[-1:] * ((1 << (k - 1).bit_length()) - k)
        rows = np.asarray(self._hot[jnp.asarray(slots, jnp.int32)],
                          np.float32)
        for (twin_id, _), row in zip(evicted, rows[:k]):
            self._cold[twin_id] = row
        self.stats.evictions += k
        self.stats.evict_reads += 1

    def fetch(self, twin_ids: Sequence[TwinId]):
        """Promote ``twin_ids`` to the hot slab and gather their state.

        Returns ``(ys, steps, thetas)``: ``ys`` a (n, D) device array of
        carried states, ``steps`` a host (n,) int64 vector of global step
        indices, ``thetas`` a (n, ...) float32 array of drive parameters
        (or None if none of the twins carries one).  All requested twins
        are pinned for the duration of the promotion, so a fetch of more
        than ``hot_capacity`` twins raises instead of thrashing.

        Twins evicted to make room are paged out together: their device
        rows are copied to host BEFORE the page-in scatter overwrites
        their slots — eviction moves state, never loses it.
        """
        ids = list(twin_ids)
        unknown = [i for i in ids if i not in self]
        if unknown:
            raise KeyError(f"unregistered twin(s): {unknown!r}")
        if len(set(ids)) != len(ids):
            raise ValueError(
                "fetch: duplicate twin ids in one batch (a twin's next "
                "window depends on its previous one — serialise them)")
        if len(ids) > self.hot_capacity:
            raise ValueError(
                f"fetch: batch of {len(ids)} exceeds hot_capacity "
                f"{self.hot_capacity}")
        pinned = set(ids)
        page_in = []                           # (slot, host_row) pairs
        evicted = []                           # (twin_id, slot) pairs
        with jax.profiler.TraceAnnotation("store.page"):
            for twin_id in ids:
                if twin_id in self._slot_of:
                    self.stats.hot_hits += 1
                    self._slot_of.move_to_end(twin_id)    # touch: now MRU
                else:
                    slot = (self._free.pop() if self._free
                            else self._evict_lru(pinned, evicted))
                    page_in.append((slot, self._cold.pop(twin_id)))
                    self._slot_of[twin_id] = slot
                    self.stats.page_ins += 1
            if evicted:
                self._page_out(evicted)
            if page_in:
                slots = jnp.asarray([s for s, _ in page_in], jnp.int32)
                rows = jnp.asarray(np.stack([r for _, r in page_in]))
                self._hot = self._hot.at[slots].set(rows)
        gather = jnp.asarray([self._slot_of[i] for i in ids], jnp.int32)
        ys = self._hot[gather]
        steps = np.asarray([self._step[i] for i in ids], np.int64)
        th = [self._theta[i] for i in ids]
        if all(t is None for t in th):
            thetas = None
        elif any(t is None for t in th):
            raise ValueError(
                "fetch: mixed drive parameters — a fleet either drives "
                "every twin (register all with theta=) or none")
        else:
            thetas = jnp.asarray(np.stack(th))
        return ys, steps, thetas

    def commit(self, twin_ids: Sequence[TwinId], ys, steps) -> None:
        """Scatter served end-states back into the hot slab and advance
        the per-twin global step counters.  ``ys`` is (n, D) (device or
        host); ``steps`` the new ABSOLUTE step indices."""
        ids = list(twin_ids)
        missing = [i for i in ids if i not in self._slot_of]
        if missing:
            raise KeyError(
                f"commit: twin(s) {missing!r} are not hot — fetch pins "
                f"a batch's twins until its commit")
        slots = jnp.asarray([self._slot_of[i] for i in ids], jnp.int32)
        self._hot = self._hot.at[slots].set(
            jnp.asarray(ys, self._hot.dtype))
        for i, s in zip(ids, np.asarray(steps, np.int64)):
            self._step[i] = int(s)
        self.stats.commits += 1

    # -- inspection (tests, checkpointing) ----------------------------------
    def export_state(self):
        """Flush the whole population to host for a snapshot:
        ``(ids, ys, steps, thetas)`` in registration order, with hot
        rows read out of the device slab (LRU order untouched).
        ``thetas`` is ``None`` for undriven populations, else a stacked
        (N, ...) float32 array."""
        ids = list(self._step)
        if not ids:
            return ids, np.zeros((0, self.state_dim), np.float32), \
                np.zeros((0,), np.int64), None
        ys = np.stack([self.peek(i)[0] for i in ids])
        steps = np.asarray([self._step[i] for i in ids], np.int64)
        th = [self._theta[i] for i in ids]
        thetas = None if all(t is None for t in th) else \
            np.stack(th).astype(np.float32)
        return ids, ys, steps, thetas

    def peek(self, twin_id: TwinId):
        """Read one twin's ``(y, step)`` without touching LRU order."""
        if twin_id not in self:
            raise KeyError(f"unregistered twin {twin_id!r}")
        if twin_id in self._slot_of:
            y = np.asarray(self._hot[self._slot_of[twin_id]], np.float32)
        else:
            y = self._cold[twin_id]
        return y, self._step[twin_id]

    def theta(self, twin_id: TwinId):
        return self._theta[twin_id]

    def check_invariants(self) -> None:
        """Structural audit used by the stress tests: every registered
        twin is in exactly one tier, slots are bijective, no state row is
        non-finite."""
        hot, cold = set(self._slot_of), set(self._cold)
        if hot & cold:
            raise AssertionError(f"twins in both tiers: {hot & cold}")
        if hot | cold != set(self._step):
            raise AssertionError("registered twins != hot + cold")
        slots = list(self._slot_of.values())
        if len(set(slots)) != len(slots):
            raise AssertionError("hot slot collision")
        if set(slots) & set(self._free):
            raise AssertionError("occupied slot on the free list")
        if len(slots) + len(self._free) != self.hot_capacity:
            raise AssertionError("slot leak: occupied + free != capacity")
        for tid in self._step:
            y, _ = self.peek(tid)
            if not np.isfinite(y).all():
                raise AssertionError(f"twin {tid!r} state went non-finite")
