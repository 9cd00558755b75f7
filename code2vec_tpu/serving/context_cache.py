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

A book may also keep a POOL OF PAGES beside the slots (`pages` of
`page_tokens` tokens each), for a model whose layers hold a context in
two geometries (models/window_moe_lm.py): a slot is then the context's
RING (the last tokens, in its window layers) and its
`ceil(tokens / page_tokens)` pages hold every token (in its full
layers). `acquire_pages` takes both at once and evicts least recently
used contexts WHOLE, ring slot and pages, until the new one fits; a
context that no eviction could make room for is refused, never cut.

With `fixed_size` AND pages (models/delta_moe_lm.py) a slot is one
state and the pages hold every token beside it; such a context can GROW:
`extend_pages` takes the free pages its new tokens need (it evicts
nothing: a turn never costs another session its place) and `replace`
puts the longer context under a NEW id in the old one's place, in one
act under the book's lock.

An id is the content's own hash (a grown context's: `extended_id`, a
hash of the id it had and the tokens it grew by, so that nothing
rehashes what the context already holds), so registering the same tokens
twice finds the slot already filled. A slot being filled belongs to no id: the
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
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from code2vec_tpu import obs

_G_SLOTS = obs.gauge(
    "latent_cache_slots_held", "cache slots that hold a registered context")
_G_TOKENS = obs.gauge(
    "latent_cache_tokens_held", "real tokens in the slots held")
_G_FILL = obs.gauge(
    "latent_cache_fill_ratio",
    "real tokens held over the capacity of all slots; for a cache of "
    "fixed-size states, slots held over slots; for rings with a page "
    "pool, pages held over pages")
_C_EVICTED = obs.counter(
    "latent_cache_evictions_total",
    "contexts that lost their slot to a newer one (least recently used)")
_C_REGISTERED = obs.counter(
    "contexts_registered_total",
    "contexts whose registration filled a slot (a repeat of a held "
    "context fills none)")
_G_PAGES = obs.gauge(
    "page_pool_pages_held",
    "pages of the full layers' pool that registered contexts hold")
_G_PAGE_FILL = obs.gauge(
    "page_pool_fill_ratio", "pages held over the pages of the pool")
_G_RINGS = obs.gauge(
    "window_ring_slots_held",
    "ring slots of the window layers that hold a registered context")


class Held(NamedTuple):
    slot: int
    tokens: int


class HeldPages(NamedTuple):
    """What a book with a page pool keeps of a context."""
    slot: int                   # its ring slot
    tokens: int
    pages: Tuple[int, ...]      # page g holds tokens [g P, (g + 1) P)


class PoolTooSmall(ValueError):
    """A context needs more pages than the whole pool has, or a turn
    more than are free."""


class TooLong(ValueError):
    """A turn would take its context past the longest one admitted."""


def context_id(ids: np.ndarray) -> str:
    """The id of a context: a hash of its token ids."""
    return hashlib.sha256(
        np.ascontiguousarray(ids, dtype=np.int32).tobytes()).hexdigest()[:16]


def extended_id(context: str, ids: np.ndarray) -> str:
    """The id of the context `context` extended by the tokens `ids`:
    sha256 over the old id's 16 hexadecimal characters (ASCII) followed
    by the tokens as little-endian int32, its first 16 hexadecimal
    characters. A client that knows a session's id and what it sent can
    compute the id the session has next."""
    return hashlib.sha256(
        context.encode("ascii") + np.ascontiguousarray(
            ids, dtype="<i4").tobytes()).hexdigest()[:16]


class ContextSlots:
    def __init__(self, slots: int, capacity: int, fixed_size: bool = False,
                 pages: int = 0, page_tokens: int = 0):
        if slots < 1 or capacity < 1:
            raise ValueError("a context cache needs at least one slot of at "
                             "least one token")
        if pages and page_tokens < 1:
            raise ValueError("a page pool needs pages of at least one token")
        self.slots, self.capacity = int(slots), int(capacity)
        self.fixed_size = bool(fixed_size)
        self.pages, self.page_tokens = int(pages), int(page_tokens)
        self._lock = threading.Lock()
        self._held: "OrderedDict[str, Held]" = OrderedDict()  # oldest first
        self._free = list(range(self.slots - 1, -1, -1))
        self._free_pages = list(range(self.pages - 1, -1, -1))
        self._publish()

    def fresh(self) -> "ContextSlots":
        """An empty book of the same geometry."""
        return ContextSlots(self.slots, self.capacity, self.fixed_size,
                            self.pages, self.page_tokens)

    def _publish(self) -> None:
        tokens = sum(h.tokens for h in self._held.values())
        _G_SLOTS.set(len(self._held))
        _G_TOKENS.set(tokens)
        if self.pages:
            held = self.pages - len(self._free_pages)
            _G_PAGES.set(held)
            _G_PAGE_FILL.set(held / self.pages)
            if not self.fixed_size:     # a slot is a ring of tokens
                _G_RINGS.set(len(self._held))
                _G_FILL.set(held / self.pages)
                return
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

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_tokens)

    def acquire_pages(self, tokens: int
                      ) -> Tuple[int, Tuple[int, ...], List[str]]:
        """A ring slot and the pages a context of `tokens` tokens needs,
        taken at once: least recently used contexts are evicted whole,
        here and now, until both are free. -> (slot, pages, evicted
        ids). Raises PoolTooSmall for a context that an EMPTY pool could
        not hold, LookupError when what is missing is being filled."""
        need = self.pages_for(tokens)
        if need > self.pages:
            raise PoolTooSmall(
                f"a context of {int(tokens)} tokens needs {need} pages of "
                f"{self.page_tokens} tokens; the pool has {self.pages}")
        with self._lock:
            evicted = []
            while not self._free or len(self._free_pages) < need:
                if not self._held:
                    self._publish()
                    raise LookupError("the ring slots or pages that are "
                                      "missing are being filled")
                context, held = self._held.popitem(last=False)
                self._free.append(held.slot)
                self._free_pages.extend(reversed(held.pages))
                evicted.append(context)
                _C_EVICTED.inc()
            slot = self._free.pop()
            pages = tuple(self._free_pages.pop() for _ in range(need))
            self._publish()
            return slot, pages, evicted

    def commit(self, slot: int, context: str, tokens: int,
               pages: Sequence[int] = ()) -> None:
        """`slot` (and `pages`), taken by `acquire` (`acquire_pages`),
        now hold all of `context`."""
        with self._lock:
            self._held[context] = (
                HeldPages(slot, int(tokens), tuple(pages)) if self.pages
                else Held(slot, int(tokens)))
            _C_REGISTERED.inc()
            self._publish()

    def release(self, slot: Optional[int], pages: Sequence[int] = ()
                ) -> None:
        """A slot (and pages) taken by `acquire` whose filling failed;
        `slot` None: pages alone, taken by `extend_pages` for a turn
        that did not happen."""
        with self._lock:
            if slot is not None:
                self._free.append(slot)
            self._free_pages.extend(reversed(tuple(pages)))
            self._publish()

    def extend_pages(self, context: str, more: int
                     ) -> Optional[Tuple[HeldPages, Tuple[int, ...]]]:
        """What `context` holds, now the most recently used, and the
        FREE pages that `more` further tokens need beyond its own (none
        while they fit its last page), taken here and now. None for an
        id that is unknown or evicted; TooLong past the longest context
        admitted; PoolTooSmall where too few pages are free (nothing is
        evicted for a turn, and nothing was taken)."""
        with self._lock:
            held = self._held.get(context)
            if held is None:
                return None
            self._held.move_to_end(context)
            tokens = held.tokens + int(more)
            if tokens > self.capacity:
                raise TooLong(
                    f"{held.tokens} tokens held and {int(more)} more pass "
                    f"the longest context admitted, {self.capacity} "
                    f"(positions)")
            need = self.pages_for(tokens) - len(held.pages)
            if need > len(self._free_pages):
                raise PoolTooSmall(
                    f"{int(more)} more tokens need {need} more pages of "
                    f"{self.page_tokens} tokens; {len(self._free_pages)} "
                    f"of the pool's {self.pages} are free (pool)")
            pages = tuple(self._free_pages.pop() for _ in range(need))
            self._publish()
            return held, pages

    def replace(self, old: str, new: str, tokens: int,
                pages: Sequence[int]) -> bool:
        """The context `old` has grown: it is now `new`, of `tokens`
        tokens on `pages` (its own and those `extend_pages` took), in
        the same slot; `old` is gone. False, and nothing changed, where
        `old` is no longer held (evicted since the lookup): the caller
        gives the pages it took back."""
        with self._lock:
            held = self._held.pop(old, None)
            if held is None:
                return False
            self._held[new] = HeldPages(held.slot, int(tokens), tuple(pages))
            self._publish()
            return True

    def held(self) -> Dict[str, Held]:
        with self._lock:
            return dict(self._held)


def chunks(tokens: int, chunk: int) -> Sequence[Tuple[int, int]]:
    """(start, real tokens) of each registration chunk of a context."""
    return [(start, min(chunk, tokens - start))
            for start in range(0, tokens, chunk)]
