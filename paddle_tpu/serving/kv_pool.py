"""Shared KV block pool: fixed-size pages, refcounts, prefix-cache reuse.

The device-side pools (``[L, P, kvh, bs, D]`` arrays owned by the engine)
are dumb storage; THIS object owns the page accounting — which physical
page belongs to whom, how many requests share it, and which freed pages
still hold reusable prefix content. vLLM-style design, host-side and
jit-free:

  * pages are ref-counted: prefix-shared pages are held by several
    sequences at once and only return to the free list at refcount 0;
  * freed pages that were registered as prompt-prefix content park in a
    CACHED state (refcount 0, content retained in the device pool, found
    again by hash) instead of being wiped — allocation evicts them LRU
    only under pressure, so a repeated system prompt never re-prefills;
  * the prefix key is a hash CHAIN over full pages of token ids (page c's
    key commits to every token before it), so a hit of depth k reuses
    exactly the first k pages of an identical prompt prefix at identical
    positions — which is the only case where cached K/V is valid (rope
    bakes absolute positions into K).

Stats are first-class (the serving metrics in profiler/instrument read
them): allocations, evictions, prefix hits/queries, utilization.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from ..resilience import chaos
from .wire import seal as _seal


class PoolExhausted(RuntimeError):
    """No free page and nothing evictable — callers defer or preempt."""


class KVBlockPool:
    """Page accounting for one engine's shared KV pools."""

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_cache: bool = True):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"KVBlockPool needs num_blocks >= 1 and block_size >= 1 "
                f"(got {num_blocks}, {block_size})")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._ref: List[int] = [0] * self.num_blocks
        # hash-chain key -> page id for reusable prefix pages; _cached is
        # the LRU of refcount-0 pages still holding registered content
        self._by_key: Dict[Tuple, int] = {}
        self._key_of: Dict[int, Tuple] = {}
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        self.stats = {"allocated": 0, "released": 0, "evicted": 0,
                      "prefix_queries": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0}

    # -- core accounting ------------------------------------------------------
    def used_blocks(self) -> int:
        """Pages held by live sequences (refcount > 0): every page is on
        the free list, parked in the prefix cache or held, so nothing is
        walked (the engine and its telemetry ask several times a step)."""
        return self.num_blocks - len(self._free) - len(self._cached)

    def cached_blocks(self) -> int:
        return len(self._cached)

    def free_blocks(self) -> int:
        """Pages allocatable without evicting cached prefix content."""
        return len(self._free)

    def available_blocks(self) -> int:
        return len(self._free) + len(self._cached)

    def utilization(self) -> float:
        return self.used_blocks() / self.num_blocks

    def allocate(self, n: int = 1) -> List[int]:
        """Take n pages (refcount 1 each), evicting LRU cached prefix pages
        under pressure. Raises PoolExhausted if fewer than n are
        obtainable; the ``serve.kv_alloc`` chaos probe fires here so the
        drill can exercise exhaustion deterministically."""
        chaos.site("serve.kv_alloc")
        if self.available_blocks() < n:
            raise PoolExhausted(
                f"KV pool exhausted: want {n} pages, "
                f"{len(self._free)} free + {len(self._cached)} cached of "
                f"{self.num_blocks}")
        return [self._take_page() for _ in range(n)]

    def _take_page(self) -> int:
        """One page off the free list (LRU-evicting a cached prefix page
        under pressure), refcount 1. Caller has proven availability; no
        chaos probe fires — ``truncate`` uses this mid-rollback, where an
        injected allocation fault could not be unwound atomically."""
        if self._free:
            blk = self._free.pop()
        else:
            blk, _ = self._cached.popitem(last=False)   # LRU evict
            self._drop_key(blk)
            self.stats["evicted"] += 1
        self._ref[blk] = 1
        self.stats["allocated"] += 1
        return blk

    def incref(self, blocks: Sequence[int]) -> None:
        for blk in blocks:
            if self._ref[blk] <= 0:
                raise ValueError(f"incref on free page {blk}")
            self._ref[blk] += 1

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per page; at 0 the page returns to the free
        list, or parks in the prefix cache if its content is registered."""
        for blk in blocks:
            if self._ref[blk] <= 0:
                raise ValueError(f"release of free page {blk}")
            self._ref[blk] -= 1
            self.stats["released"] += 1
            if self._ref[blk] == 0:
                if blk in self._key_of and self.enable_prefix_cache:
                    self._cached[blk] = None
                    self._cached.move_to_end(blk)
                else:
                    self._drop_key(blk)
                    self._free.append(blk)

    def _drop_key(self, blk: int) -> None:
        key = self._key_of.pop(blk, None)
        if key is not None and self._by_key.get(key) == blk:
            del self._by_key[key]

    def drop_cache(self) -> int:
        """Forget every registered prefix: parked cached pages return to
        the free list and ALL keys are dropped (pages still referenced
        by live sequences keep their refcounts, they just stop being
        prefix-matchable). The step-fault containment reset calls this
        when device pool content can no longer be trusted — a stale
        prefix hit would silently serve garbage K/V. Returns how many
        parked pages were freed."""
        freed = 0
        while self._cached:
            blk, _ = self._cached.popitem(last=False)
            self._free.append(blk)
            freed += 1
        for blk in list(self._key_of):
            self._drop_key(blk)
        return freed

    def truncate(self, pages: Sequence[int], n_tokens: int
                 ) -> Tuple[List[int], int, Optional[Tuple[int, int]]]:
        """Roll one sequence's page list back so it covers exactly
        ``n_tokens`` cached positions — the speculative-decode rollback:
        pages past the accepted prefix return to the pool. Returns
        ``(kept_pages, released, cow)``:

          * ``kept_pages`` — the new page list (``ceil(n_tokens / bs)``
            pages, a prefix of ``pages`` except possibly its last entry);
          * ``released``   — trailing pages dropped past the kept prefix
            (the COW exchange below is not counted: it frees and takes
            one page, net zero);
          * ``cow``        — ``None``, or ``(old, new)`` when the kept
            BOUNDARY page (only partially covered, so the sequence will
            rewrite its tail slots on the next feeds) is shared: held by
            another sequence (refcount > 1) or registered in the prefix
            cache, where a later request could acquire it at any moment.
            Rollback must never mutate a page someone else can read, so
            the boundary goes copy-on-write: the caller owns ``new``
            (refcount 1, unregistered) and must copy the device-pool
            content of ``old`` into it before the next scatter; ``old``
            keeps serving its other holders untouched.

        Raises PoolExhausted only on the (engine-unreachable) COW path
        when no page would be obtainable for the private copy — checked
        BEFORE any state changes, so a failed truncate leaves the pool
        and the caller's page list exactly as they were."""
        if n_tokens < 0:
            raise ValueError(f"truncate to negative coverage {n_tokens}")
        keep = -(-n_tokens // self.block_size)
        if keep > len(pages):
            raise ValueError(
                f"truncate to {n_tokens} tokens needs {keep} pages but "
                f"the sequence holds only {len(pages)}")
        kept = list(pages[:keep])
        tail = list(pages[keep:])
        blk = kept[-1] if n_tokens % self.block_size and kept else None
        need_cow = blk is not None and (self._ref[blk] > 1
                                        or blk in self._key_of)
        if need_cow:
            # releasing the tail only frees pages this sequence holds
            # the LAST reference to; prove the copy is obtainable before
            # mutating anything (atomicity: fail ⇒ nothing changed)
            obtainable = self.available_blocks() \
                + sum(1 for t in tail if self._ref[t] == 1)
            if obtainable < 1:
                raise PoolExhausted(
                    "KV pool exhausted: no page obtainable for the "
                    "copy-on-write rollback of a shared boundary page")
        if tail:
            self.release(tail)
        cow = None
        if need_cow:
            new = self._take_page()
            self.release([blk])
            kept[-1] = new
            cow = (blk, new)
        return kept, len(tail), cow

    # -- KV-page handoff (disaggregated serving) ------------------------------
    def export_pages(self, pages: Sequence[int], token_ids: Sequence[int],
                     n_tokens: int) -> dict:
        """Accounting half of a prefill→decode KV-page handoff export:
        the record a decode-pool replica's ``import_pages`` consumes.
        ``pages`` must cover exactly ``n_tokens`` cached positions of
        ``token_ids`` (full pages plus at most one partial boundary
        page). The record carries the page COUNT and geometry, the
        hash-chain keys of the FULL pages (so the importing pool can
        re-register the prefix and the router can affinity-match the
        hand-off), and the token ids those full pages hold — page
        CONTENTS ride next to it as device arrays (the engine's half;
        see ``ServingEngine._export_request``). Pure read: refcounts
        stay with the exporting request until its engine releases them
        after the device gather."""
        if n_tokens < 0:
            raise ValueError(f"export of negative coverage {n_tokens}")
        need = -(-n_tokens // self.block_size)
        if need != len(pages):
            raise ValueError(
                f"export of {n_tokens} tokens needs exactly {need} pages, "
                f"got {len(pages)}")
        full = n_tokens // self.block_size
        tokens = [int(t) for t in token_ids[:full * self.block_size]]
        return _seal({
            "version": 1,
            "num_pages": len(pages),
            "n_tokens": int(n_tokens),
            "block_size": self.block_size,
            # full-page chain keys: the prefix identity the import
            # re-registers and the router's decode-pool affinity signal
            "keys": self._chain_keys(tokens, self.block_size),
            "tokens": tokens,
        }, "kv_export_record")

    def unregister(self, pages: Sequence[int]) -> None:
        """Drop the prefix keys of the given pages (their content can no
        longer be trusted — e.g. a hand-off import whose device scatter
        failed after ``import_pages`` registered them): a later
        ``release`` frees them instead of parking garbage-content pages
        where ``match_prefix`` would serve them as valid K/V."""
        for blk in pages:
            self._drop_key(blk)

    def import_pages(self, record: dict) -> List[int]:
        """Take ownership of one exported hand-off in THIS pool:
        allocates ``num_pages`` fresh pages (refcount 1 each — the
        importing request owns them) and re-registers the full pages'
        hash-chain prefix keys, so the prefix travels WITH the K/V and
        future same-prefix arrivals at the decode replica hit the cache.
        Returns the new page list in export order (the engine scatters
        the device contents into these slots). Raises ``PoolExhausted``
        (or lets a ``serve.kv_alloc`` chaos fault through) when the
        pages are not obtainable — the caller falls back to prompt
        recompute, never a torn import: allocation is all-or-nothing
        and nothing else mutates before it succeeds."""
        _seal(record, "kv_export_record")
        if record["block_size"] != self.block_size:
            raise ValueError(
                f"hand-off at block_size {record['block_size']} "
                f"cannot import into a pool at {self.block_size}")
        pages = self.allocate(record["num_pages"]) \
            if record["num_pages"] else []
        full = record["n_tokens"] // self.block_size
        if full and record["tokens"]:
            self.register_prefix(record["tokens"], pages[:full])
        return pages

    # -- prefix cache ---------------------------------------------------------
    @staticmethod
    def _chain_keys(token_ids: Sequence[int], block_size: int):
        """Hash-chain keys for each FULL page of token_ids. Keys hash
        only ints/tuples, so they are stable across processes and
        PYTHONHASHSEED values — the replica router's drain manifests
        carry them through JSON as the affinity hand-off signal."""
        keys = []
        parent = ()
        for c in range(len(token_ids) // block_size):
            page = tuple(token_ids[c * block_size:(c + 1) * block_size])
            parent = (hash((parent, page)), page[0], c)
            keys.append(parent)
        return keys

    def match_prefix(self, token_ids: Sequence[int],
                     max_tokens: Optional[int] = None
                     ) -> Tuple[List[int], int]:
        """Longest cached full-page prefix of token_ids. Returns (pages,
        n_tokens); the pages are increfed (caller owns a reference — put
        them at the front of the sequence's page list and ``release`` with
        the rest). ``max_tokens`` caps the hit (the engine keeps at least
        one prompt token uncached so prefill still yields last-token
        logits)."""
        self.stats["prefix_queries"] += 1
        if not self.enable_prefix_cache:
            return [], 0
        limit = len(token_ids) if max_tokens is None else max_tokens
        pages: List[int] = []
        for i, key in enumerate(self._chain_keys(token_ids,
                                                 self.block_size)):
            if (i + 1) * self.block_size > limit:
                break
            blk = self._by_key.get(key)
            if blk is None:
                break
            pages.append(blk)
        for blk in pages:
            if self._ref[blk] == 0:
                self._cached.pop(blk, None)
            self._ref[blk] += 1
        n = len(pages) * self.block_size
        if pages:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += n
        return pages, n

    def register_prefix(self, token_ids: Sequence[int],
                        pages: Sequence[int]) -> None:
        """Record that ``pages[c]`` holds the K/V of token_ids' c-th full
        page (positions c*bs..), making them reusable after release. First
        registration of a key wins — an identical prompt racing in keeps
        its private copy unregistered."""
        if not self.enable_prefix_cache:
            return
        for key, blk in zip(self._chain_keys(token_ids, self.block_size),
                            pages):
            if key in self._by_key:
                continue
            if blk in self._key_of:      # page re-registered under new key
                self._drop_key(blk)
            self._by_key[key] = blk
            self._key_of[blk] = key


def prefix_chain_keys(token_ids: Sequence[int], block_size: int
                      ) -> List[Tuple]:
    """Public spelling of the pool's hash-chain prefix keys: one key per
    FULL page of ``token_ids``, each committing to every token before it.
    Two prompts share a key exactly when they share that page-aligned
    prefix — which is both when cached K/V is reusable (kv_pool) and
    when routing them to the same replica pays (serving/router.py)."""
    return KVBlockPool._chain_keys(token_ids, block_size)


__all__ = ["KVBlockPool", "PoolExhausted", "prefix_chain_keys"]
