"""Who holds which slot of a device-resident context cache.

A cache of `slots` slots lives on the device (the model owns the
arrays); this is the host's book of it: which context id sits in which
slot with how many tokens, which slot a new context gets (a free one,
else the least recently used), and what a request that names an id may
read. It touches no array. A slot is either `capacity` TOKENS long (a
row of the cache a token) or, with `fixed_size`, one STATE whatever the
tokens behind it: `capacity` is then only the longest context admitted,
and the fill gauge reads slots held over slots (a share of token rows
means nothing where a context of 8 and one of 32 thousand tokens take
the same bytes).

An id is the content's own hash, so registering the same tokens twice
finds the slot already filled. A slot being filled belongs to no id: the
id it held is gone the moment the slot is taken, and the new id appears
only when `commit` says every chunk is written. A lookup therefore
never sees a half-written slot, and a request for an evicted id gets
`None`, never another context's tokens.

Thread-safe; the methods are short and hold one lock.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from code2vec_tpu import obs

_G_SLOTS = obs.gauge(
    "latent_cache_slots_held", "cache slots that hold a registered context")
_G_TOKENS = obs.gauge(
    "latent_cache_tokens_held", "real tokens in the slots held")
_G_FILL = obs.gauge(
    "latent_cache_fill_ratio",
    "real tokens held over the capacity of all slots; for a cache of "
    "fixed-size states, slots held over slots")
_C_EVICTED = obs.counter(
    "latent_cache_evictions_total",
    "contexts that lost their slot to a newer one (least recently used)")
_C_REGISTERED = obs.counter(
    "contexts_registered_total",
    "contexts whose registration filled a slot (a repeat of a held "
    "context fills none)")


class Held(NamedTuple):
    slot: int
    tokens: int


def context_id(ids: np.ndarray) -> str:
    """The id of a context: a hash of its token ids."""
    return hashlib.sha256(
        np.ascontiguousarray(ids, dtype=np.int32).tobytes()).hexdigest()[:16]


class ContextSlots:
    def __init__(self, slots: int, capacity: int, fixed_size: bool = False):
        if slots < 1 or capacity < 1:
            raise ValueError("a context cache needs at least one slot of at "
                             "least one token")
        self.slots, self.capacity = int(slots), int(capacity)
        self.fixed_size = bool(fixed_size)
        self._lock = threading.Lock()
        self._held: "OrderedDict[str, Held]" = OrderedDict()  # oldest first
        self._free = list(range(self.slots - 1, -1, -1))
        self._publish()

    def _publish(self) -> None:
        tokens = sum(h.tokens for h in self._held.values())
        _G_SLOTS.set(len(self._held))
        _G_TOKENS.set(tokens)
        _G_FILL.set(len(self._held) / self.slots if self.fixed_size
                    else tokens / (self.slots * self.capacity))

    def lookup(self, context: str) -> Optional[Held]:
        """The slot and length of a registered context, now the most
        recently used; None for an id that is unknown or evicted."""
        with self._lock:
            held = self._held.get(context)
            if held is not None:
                self._held.move_to_end(context)
            return held

    def acquire(self) -> Tuple[int, Optional[str]]:
        """A slot to fill: a free one, else the least recently used
        context's, which is evicted here and now. -> (slot, evicted id).
        Raises LookupError when every slot is being filled."""
        with self._lock:
            if self._free:
                return self._free.pop(), None
            if not self._held:
                raise LookupError("every cache slot is being filled")
            context, held = self._held.popitem(last=False)
            _C_EVICTED.inc()
            self._publish()
            return held.slot, context

    def commit(self, slot: int, context: str, tokens: int) -> None:
        """`slot`, taken by `acquire`, now holds all of `context`."""
        with self._lock:
            self._held[context] = Held(slot, int(tokens))
            _C_REGISTERED.inc()
            self._publish()

    def release(self, slot: int) -> None:
        """A slot taken by `acquire` whose filling failed."""
        with self._lock:
            self._free.append(slot)

    def held(self) -> Dict[str, Held]:
        with self._lock:
            return dict(self._held)


def chunks(tokens: int, chunk: int) -> Sequence[Tuple[int, int]]:
    """(start, real tokens) of each registration chunk of a context."""
    return [(start, min(chunk, tokens - start))
            for start in range(0, tokens, chunk)]
