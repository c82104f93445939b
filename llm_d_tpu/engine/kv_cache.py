"""Paged KV-cache block management: allocator, prefix cache, eviction.

Host-side bookkeeping for the device-resident paged cache (the device arrays
live in the engine; this module deals only in block ids).  Design follows
vLLM's prefix-caching allocator semantics — full blocks are content-hashed
(chain scheme, ``llm_d_tpu.utils.hashing``) and kept after free in an LRU
evictor so later requests with a shared prefix reuse them — because the
scheduler-side prefix scorers (reference: gaie values, SURVEY.md §2.4) are
calibrated against exactly this behavior.

Regions (SPMD data parallelism): with ``num_regions = dp > 1`` the pool is
partitioned so region ``r`` owns global blocks [r*B_l, (r+1)*B_l), whose
device rows live in dp-shard ``r`` of the engine's stacked cache.  A request
is pinned to one region at admission (``assign_region``) so every page it
touches is shard-local — device attention never crosses the dp axis (the
reference's per-rank KV in vLLM DP engine cores, wide-ep decode.yaml:73-93).
Block ids stay GLOBAL on the host: region / local ids are pure arithmetic
(``block // B_l``, ``block % B_l``).  Each region's local block 0 is its
null/trash block (padding rows of that shard's batch scatter there) and is
never allocated; with one region this is the classic reserved block 0.

Groups (a stack of window and full layers, ``ModelConfig.kv_cache_groups``,
where the engine's limits make the window layers' group smaller than one pool
and nothing asked for knows one group alone, ``engine.derive_group_blocks``):
the manager holds a LIST of page pools, one a layer kind, each with its own
pages, free list, content hashes and LRU.  Group 0 is the full layers': a
request's ``block_ids``, every token's page, as in a stack of one kind,
which has this group alone.  Group 1 is the window layers': a request's
``window_block_ids`` run entry for entry beside ``block_ids`` and hold a
page only from the window's first visible token on: ``release_passed``
gives back the pages no later query can see once the step that last read
them is launched (the device runs programs in order, so whoever gets the
page next writes it after that read), and an entry given back is 0, the
trash page, which the kernels never read because their walk starts at the
first visible page.  A prefix hit of n blocks needs the full group's n
blocks and the window group's blocks under the last ``sliding_window - 1``
tokens before the boundary; ``find_cached_prefix`` grants the longest n both
have, so an eviction in either group shortens a hit and never corrupts it.
Both groups hash with the one chain of the request's tokens.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from llm_d_tpu.engine.request import Request
from llm_d_tpu.utils.hashing import hash_block

# Event callbacks for the KV-event stream and tiered offload
# (block_hash bytes, block_id) -> None
BlockEvent = Callable[[bytes, int], None]


class BlockPool:
    """One group's pages: free lists, reference counts, content hashes and
    the LRU of free-but-cached pages, by region.  Each region's local block
    0 is its trash page and is never handed out."""

    def __init__(self, name: str, num_blocks: int, num_regions: int,
                 caching: bool) -> None:
        assert num_blocks >= 2 * num_regions
        assert num_blocks % num_regions == 0, \
            f"num_blocks {num_blocks} not divisible by {num_regions} regions"
        self.name = name
        self.num_blocks = num_blocks
        self.num_regions = num_regions
        self.blocks_per_region = B_l = num_blocks // num_regions
        self.caching = caching
        self.free: List[collections.deque[int]] = [
            collections.deque(range(r * B_l + 1, (r + 1) * B_l))
            for r in range(num_regions)]
        self.ref: Dict[int, int] = {}                    # block -> refcount
        self.hash_of: Dict[int, bytes] = {}              # block -> content hash
        self.cached: Dict[bytes, int] = {}               # hash -> block
        # Free-but-cached blocks in LRU order (oldest first), per region.
        self.evictor: List["collections.OrderedDict[int, None]"] = [
            collections.OrderedDict() for _ in range(num_regions)]
        self.eviction_count = 0
        self.on_removed: List[BlockEvent] = []

    def region_of(self, block_id: int) -> int:
        return block_id // self.blocks_per_region

    def free_blocks(self, region: int) -> int:
        return len(self.free[region]) + len(self.evictor[region])

    @property
    def num_free(self) -> int:
        return sum(len(f) for f in self.free) \
            + sum(len(e) for e in self.evictor)

    @property
    def pages_held(self) -> int:
        """Pages referenced by a request or kept for a later hit."""
        return len(self.ref) + sum(len(e) for e in self.evictor)

    def take(self, protected: frozenset = frozenset(),
             region: int = 0) -> Optional[int]:
        """Claim a block in ``region``: plain free first, else evict the LRU
        cached block not in ``protected``."""
        free = self.free[region]
        evictor = self.evictor[region]
        while free:
            b = free.popleft()
            if b not in evictor:            # plain free block
                return b
        victim = next((b for b in evictor if b not in protected), None)
        if victim is not None:              # evict LRU cached block
            del evictor[victim]
            h = self.hash_of.pop(victim, None)
            if h is not None and self.cached.get(h) == victim:
                del self.cached[h]
                self.eviction_count += 1
                for cb in self.on_removed:
                    cb(h, victim)
            return victim
        return None

    def acquire(self, b: int) -> None:
        """One more reference to ``b`` (out of the LRU if it waited there)."""
        self.evictor[self.region_of(b)].pop(b, None)
        self.ref[b] = self.ref.get(b, 0) + 1

    def release(self, b: int) -> None:
        self.ref[b] -= 1
        if self.ref[b] == 0:
            del self.ref[b]
            if self.caching and b in self.hash_of:
                # Keep cached, evict LRU later.
                self.evictor[self.region_of(b)][b] = None
            else:
                self.free[self.region_of(b)].append(b)

    def store(self, b: int, h: bytes) -> bool:
        """Register ``b`` as the page of content ``h``; False where the
        page has a hash already or another page is that content's."""
        if b in self.hash_of or h in self.cached:
            return False
        self.hash_of[b] = h
        self.cached[h] = b
        return True

    def uncache(self, b: int) -> None:
        h = self.hash_of.pop(b, None)
        if h is not None and self.cached.get(h) == b:
            del self.cached[h]
        evictor = self.evictor[self.region_of(b)]
        if b in evictor:
            del evictor[b]
            self.free[self.region_of(b)].append(b)


class KVCacheManager:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        hash_seed: str = "42",
        num_regions: int = 1,
        state_slots: int = 0,
        window_blocks: int = 0,
        sliding_window: int = 0,
    ) -> None:
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.hash_seed = hash_seed
        # Group 0: the pages every layer of a one-kind stack, or the full
        # layers of a grouped one, hold.  Group 1 (``window_blocks`` > 0):
        # the window layers' (module docstring).
        self.groups: List[BlockPool] = [BlockPool(
            "full", num_blocks, num_regions, enable_prefix_caching)]
        self.sliding_window = sliding_window
        if window_blocks:
            assert num_regions == 1 and sliding_window >= 1
            self.groups.append(BlockPool(
                "window", window_blocks, 1, enable_prefix_caching))
        # Blocks that hold the keys a query at a block boundary sees under
        # the window, itself excluded: what a prefix hit needs of group 1.
        self._tail_blocks = -(-(sliding_window - 1) // block_size)
        full = self.groups[0]
        self.num_blocks = num_blocks
        self.num_regions = num_regions
        self.blocks_per_region = full.blocks_per_region
        # Group 0's books under the names they always had.
        self._free, self._ref, self._hash_of = full.free, full.ref, full.hash_of
        self._cached, self._evictor = full.cached, full.evictor
        # Per-request chain of block hashes (computed lazily).
        self._req_hashes: Dict[str, List[bytes]] = {}
        self._region_of_req: Dict[str, int] = {}

        # KV events / offload: they describe group 0's blocks.
        self.on_block_stored: List[BlockEvent] = []
        self.on_block_removed: List[BlockEvent] = full.on_removed
        # Tiered cache: consulted on device-cache miss with (block_hash,
        # protected chain blocks, target region); returns a restored
        # (cached, evictor-parked) block id in that region or None
        # (engine/offload.py).
        self.secondary_lookup: Optional[
            Callable[[bytes, frozenset, int], Optional[int]]] = None
        # Grouped cache: window pages given back as the window passed them,
        # and the tokens of prefix hits one group could grant and the other
        # had evicted, by the group that lost them (found at a request's
        # look-up, counted once, when its first pages are attached).
        self.window_pages_released = 0
        self.hit_tokens_lost: Dict[str, int] = {g.name: 0 for g in self.groups}
        self._lost_of_req: Dict[str, Tuple[int, int]] = {}
        # Slots of the engine's recurrent-state pool (a stack with a
        # state-space mixer; 0: none): a request takes one with its first
        # pages and drops it with them, at finish, abort and preemption
        # alike, so a slot's life is the life of the request's block list.
        # Slot 0 is the pool's trash slot.  Nothing is cleaned: the step
        # program zeroes a row's state where its chunk starts at position
        # 0, which a preempted request's recompute does too.
        self.num_state_slots = state_slots
        self._free_state_slots: List[int] = list(range(state_slots, 0, -1))

    # ---------- introspection ----------

    @property
    def eviction_count(self) -> int:
        return sum(g.eviction_count for g in self.groups)

    @property
    def state_slots_in_use(self) -> int:
        return self.num_state_slots - len(self._free_state_slots)

    def region_of_block(self, block_id: int) -> int:
        return block_id // self.blocks_per_region

    def local_block_id(self, block_id: int) -> int:
        return block_id % self.blocks_per_region

    def region_of_request(self, request: Request) -> int:
        return self._region_of_req.get(request.request_id, 0)

    @property
    def num_free_blocks(self) -> int:
        return self.groups[0].num_free

    def region_free_blocks(self, region: int) -> int:
        return self.groups[0].free_blocks(region)

    def free_blocks_for(self, request: Request) -> int:
        """Blocks the request's list can still grow by: what the emptiest
        group has free in its region (a grouped cache attaches a page of
        every group for every new block)."""
        region = self.region_of_request(request)
        return min(g.free_blocks(region) for g in self.groups)

    def has_room(self, n: int) -> bool:
        """Every group has ``n`` blocks free."""
        return all(g.num_free >= n for g in self.groups)

    @property
    def max_request_blocks(self) -> int:
        """Largest block count any single request can ever hold (one
        region's allocatable capacity)."""
        return self.blocks_per_region - 1

    @property
    def usage(self) -> float:
        """Of group 0: the group whose demand grows with the context."""
        usable = self.num_blocks - self.num_regions
        return 1.0 - self.num_free_blocks / usable if usable else 0.0

    # ---------- prefix cache ----------

    def request_block_hashes(self, request: Request) -> List[bytes]:
        """Chain hashes of every full block of the request's confirmed
        tokens: the placeholder of a token still being sampled
        (``Request.inflight_token_ids``) is never hashed."""
        hashes = self._req_hashes.setdefault(request.request_id, [])
        tokens = request.prompt_token_ids + request.output_token_ids
        n_full = len(tokens) // self.block_size
        parent = hashes[-1] if hashes else None
        for i in range(len(hashes), n_full):
            chunk = tokens[i * self.block_size:(i + 1) * self.block_size]
            parent = hash_block(parent, chunk, self.hash_seed)
            hashes.append(parent)
        return hashes[:n_full]

    def assign_region(self, request: Request) -> int:
        """Pin the request to a region: the cached-prefix-chain region wins
        (the in-engine analogue of the EPP's prefix-affinity scorer) —
        but ONLY while that region can still hold the request's remaining
        fresh blocks; otherwise most-free wins.  A pin sticks while the
        request holds blocks; ``unpin`` lets an unplaceable request be
        re-routed on the next scheduling pass instead of starving the
        queue head against one full region."""
        rid = request.request_id
        r = self._region_of_req.get(rid)
        if r is not None:
            return r
        if self.num_regions == 1:
            self._region_of_req[rid] = 0
            return 0
        chain_region: Optional[int] = None
        chain_len = 0
        if self.enable_prefix_caching:
            for h in self.request_block_hashes(request):
                b = self._cached.get(h)
                if b is None:
                    break
                reg = self.region_of_block(b)
                if chain_region is None:
                    chain_region = reg
                elif reg != chain_region:
                    break           # chain crosses regions: stop at boundary
                chain_len += 1
        most_free = max(range(self.num_regions), key=self.region_free_blocks)
        best_r = most_free
        if chain_region is not None and chain_len > 0:
            fresh_needed = max(
                0, -(-request.num_tokens // self.block_size)
                - chain_len)
            if self.region_free_blocks(chain_region) >= fresh_needed:
                best_r = chain_region
        self._region_of_req[rid] = best_r
        return best_r

    def unpin(self, request: Request) -> bool:
        """Drop a block-less request's region pin so the next pass may
        assign a different region (used after a failed first allocation —
        affinity must not beat admission)."""
        if request.block_ids:
            return False
        self._region_of_req.pop(request.request_id, None)
        return True

    def find_cached_prefix(self, request: Request) -> Tuple[List[int], int]:
        """Longest cached block-prefix for this request within its region.

        Returns (block_ids, num_cached_tokens). Does NOT take refs yet —
        call ``allocate`` with these as ``reuse_blocks``.
        """
        if not self.enable_prefix_caching:
            return [], 0
        region = self.assign_region(request)
        blocks: List[int] = []
        for h in self.request_block_hashes(request):
            b = self._cached.get(h)
            if b is not None and self.region_of_block(b) != region:
                b = None            # foreign-shard block: unusable here
            if b is None and self.secondary_lookup is not None:
                # Host-tier restore on miss; earlier chain blocks are
                # refcount-0 evictor residents and must not be reused as
                # the restore target (silent chain corruption).
                b = self.secondary_lookup(h, frozenset(blocks), region)
                if b is not None and self.region_of_block(b) != region:
                    b = None
            if b is None:
                break
            blocks.append(b)
        # Never mark the whole sequence computed: the final token must be
        # (re)computed to produce logits for sampling.  num_tokens (not
        # num_prompt_tokens) so a RESUME admission — output_token_ids
        # pre-populated from the relay journal — restores through the
        # generated region too; for fresh requests the two are equal.
        max_cacheable = (request.num_tokens - 1) // self.block_size
        blocks = blocks[:max_cacheable + 1]
        n = len(blocks) * self.block_size
        if n >= request.num_tokens:
            blocks = blocks[:max_cacheable]
            n = len(blocks) * self.block_size
        if len(self.groups) > 1:
            blocks = blocks[:self._both_grant(request, len(blocks))]
            n = len(blocks) * self.block_size
        return blocks, n

    def caches_block(self, request: Request, i: int) -> bool:
        """Is the ``i``-th full block of the request's tokens cached (group
        0)?  One look and no walk: what a scheduler pass may ask about a
        waiting request whose look-up it keeps."""
        hashes = self._req_hashes.get(request.request_id, ())
        return 0 <= i < len(hashes) and hashes[i] in self._cached

    def same_block(self, a: Request, b: Request, i: int) -> bool:
        """Do both requests' tokens agree up to the end of their ``i``-th
        full block (by the chain hashes each has had computed)?"""
        ha = self._req_hashes.get(a.request_id, ())
        hb = self._req_hashes.get(b.request_id, ())
        return 0 <= i < min(len(ha), len(hb)) and ha[i] == hb[i]

    def holds_prefix(self, request: Request, blocks: Sequence[int]) -> bool:
        """Are ``blocks``, a hit that ``find_cached_prefix`` granted the
        request earlier, still its first pages: each the page of its
        content in the request's region, and (a grouped cache) the window
        group's pages under the window before the boundary still cached?"""
        n = len(blocks)
        hashes = self.request_block_hashes(request)
        region = self.region_of_request(request)
        full = self.groups[0]
        if n > len(hashes) or any(
                full.hash_of.get(b) != h or full.region_of(b) != region
                for b, h in zip(blocks, hashes)):
            return False
        if len(self.groups) > 1:
            cached = self.groups[1].cached
            return all(h in cached
                       for h in hashes[n - min(self._tail_blocks, n):n])
        return True

    def _both_grant(self, request: Request, n_full: int) -> int:
        """The longest hit, in blocks, that the window group grants too:
        the largest n <= ``n_full`` whose last ``_tail_blocks`` blocks (all
        n, where the hit is shorter than the window) group 1 has cached.
        Notes what either group lost the request (``hit_tokens_lost``)."""
        hashes = self.request_block_hashes(request)
        cached, k = self.groups[1].cached, self._tail_blocks
        cap = min(len(hashes), (request.num_tokens - 1) // self.block_size)
        run = both = alone = 0
        for i in range(cap):
            run = run + 1 if hashes[i] in cached else 0
            if run >= min(k, i + 1):
                alone = i + 1               # what the window group grants
                if i < n_full:
                    both = i + 1
        self._lost_of_req[request.request_id] = (
            max(alone - n_full, 0) * self.block_size,
            (n_full - both) * self.block_size)
        return both

    # ---------- allocation ----------

    def _take_free_block(self, region: int = 0) -> Optional[int]:
        # Ownership handoff by design: the caller (allocate) owns the
        # rollback — _release on partial-allocation failure.
        # llmd: ignore[PAIR002] handoff wrapper; allocate() rolls back
        return self.take_block(region=region)

    def take_block(self, protected: frozenset = frozenset(),
                   region: int = 0) -> Optional[int]:
        """Claim a block of group 0 in ``region``: plain free first, else
        evict the LRU cached block not in ``protected`` (the offload tier
        protects the prefix chain it is mid-way through assembling)."""
        return self.groups[0].take(protected, region)

    def can_allocate(self, n: int, region: Optional[int] = None) -> bool:
        if region is None:
            if self.num_regions == 1:
                region = 0
            else:
                return max(self.region_free_blocks(r)
                           for r in range(self.num_regions)) >= n
        return all(g.free_blocks(region) >= n for g in self.groups)

    def allocate(self, request: Request, num_tokens_after: int,
                 reuse_blocks: Sequence[int] = ()) -> Optional[List[int]]:
        """Grow the request's block list to cover ``num_tokens_after`` tokens.

        ``reuse_blocks`` are prefix-cache hits to adopt (only valid when the
        request currently holds no blocks). Returns newly attached block ids
        (reused + fresh), or None if not enough free blocks (caller preempts)
        or the hit is no longer whole (``holds_prefix``: the look-up may be
        older than this pass, and an evicted page is someone else's now).
        A grouped cache attaches a page of every group for every new block,
        or nothing: of a hit the window group lends the blocks under the
        window before the boundary (``find_cached_prefix`` saw them cached).
        """
        region = self.assign_region(request)
        needed_blocks = -(-num_tokens_after // self.block_size)
        new_needed = needed_blocks - len(request.block_ids)
        if new_needed <= 0:
            if self.num_state_slots:
                self._take_state_slot(request)
            return []
        reuse: List[List[int]] = [list(reuse_blocks)]
        if reuse_blocks:
            assert not request.block_ids
            if not self.holds_prefix(request, reuse_blocks):
                return None         # evicted since the look-up
            new_needed -= len(reuse_blocks)
            if len(self.groups) > 1:
                n = len(reuse_blocks)
                tail = min(self._tail_blocks, n)
                hashes = self.request_block_hashes(request)
                reuse.append([0] * (n - tail) + [
                    self.groups[1].cached[h] for h in hashes[n - tail:n]])
        elif len(self.groups) > 1:
            reuse.append([])
        for g, attach in zip(self.groups, reuse):
            evictor = g.evictor[region]
            if new_needed > 0 and g.free_blocks(region) - sum(
                    1 for b in attach if b in evictor) < new_needed:
                return None
        # Take refs on reused blocks (possibly resurrecting from evictor).
        for g, attach in zip(self.groups, reuse):
            for b in attach:
                if b:
                    g.acquire(b)
        for g, attach in zip(self.groups, reuse):
            for _ in range(max(0, new_needed)):
                b = g.take(region=region)
                if b is None:   # raced with evictor bookkeeping; roll back
                    for gg, got in zip(self.groups, reuse):
                        for bb in got:
                            if bb:
                                gg.release(bb)
                    return None
                g.ref[b] = 1
                attach.append(b)
        request.block_ids.extend(reuse[0])
        if len(self.groups) > 1:
            if reuse_blocks:
                request.window_first_block = len(reuse_blocks) - tail
            request.window_block_ids.extend(reuse[1])
            lost = self._lost_of_req.pop(request.request_id, None)
            if lost:
                self.hit_tokens_lost["full"] += lost[0]
                self.hit_tokens_lost["window"] += lost[1]
        if self.num_state_slots:
            self._take_state_slot(request)
        return reuse[0]

    def release_passed(self, request: Request) -> int:
        """Give back the request's window pages that no later query can
        see: those wholly below the first key the query at position
        ``num_computed_tokens`` sees.  The engine calls it once the step
        that computed up to there is LAUNCHED, never before: the device
        runs programs in order, so the page's next owner writes it after
        that step's reads (engine.py, ``_launch``).  A page whose tokens
        are confirmed is hashed as it goes, so that it can serve a later
        hit from the LRU.  Returns the pages given back."""
        if len(self.groups) == 1:
            return 0
        ids = request.window_block_ids
        first = min(max(request.num_computed_tokens - self.sliding_window + 1,
                        0) // self.block_size, len(ids))
        if first <= request.window_first_block:
            return 0
        win = self.groups[1]
        hashes = (self.request_block_hashes(request)
                  if self.enable_prefix_caching else ())
        n = 0
        for i in range(request.window_first_block, first):
            b = ids[i]
            if b:
                if i < len(hashes):
                    win.store(b, hashes[i])
                win.release(b)
                ids[i] = 0
                n += 1
        request.window_first_block = first
        self.window_pages_released += n
        return n

    def _take_state_slot(self, request: Request) -> None:
        # (One is always free: the scheduler runs at most as many requests
        # as the pool has slots.)
        if request.state_slot == 0:
            request.state_slot = self._free_state_slots.pop()

    def _release(self, b: int) -> None:
        self.groups[0].release(b)

    def free(self, request: Request) -> None:
        for b in reversed(request.block_ids):
            self._release(b)
        request.block_ids = []
        if request.window_block_ids:
            # Oldest first: the pages nearest the request's end, which a
            # later hit needs, are the last the LRU gives up.
            for b in request.window_block_ids[request.window_first_block:]:
                if b:
                    self.groups[1].release(b)
            request.window_block_ids = []
        request.window_first_block = 0
        if request.state_slot:
            self._free_state_slots.append(request.state_slot)
            request.state_slot = 0
        self._req_hashes.pop(request.request_id, None)
        self._region_of_req.pop(request.request_id, None)
        self._lost_of_req.pop(request.request_id, None)

    def release_tail(self, request: Request, blocks: Sequence[int]) -> None:
        """Give back just-attached tail blocks (speculative over-allocation
        rollback: the multistep fast path pre-allocates K tokens of blocks
        and must not hold them when it falls back to single-step)."""
        assert len(self.groups) == 1, "one group: a grouped cache serves " \
            "the classic step path alone"
        for b in reversed(blocks):
            assert request.block_ids and request.block_ids[-1] == b
            request.block_ids.pop()
            self._release(b)

    def trim_request(self, request: Request, num_tokens: int) -> int:
        """Shrink the request's block list to exactly cover ``num_tokens``
        tokens, releasing the tail — the spec-decode rejection rollback.

        A draft-and-verify step allocates blocks for up to K+1 tokens; the
        accepted count decides how many were really appended, so the tail
        blocks past ``ceil(num_tokens / block_size)`` go back to the pool
        the SAME step (block-boundary-safe: a partially-filled kept block
        is never released, and released tail blocks were never full, hence
        never content-hashed — the prefix cache only ever indexes accepted
        content).  Returns the number of blocks released."""
        assert len(self.groups) == 1, "one group: a grouped cache serves " \
            "the classic step path alone"
        keep = -(-num_tokens // self.block_size)
        released = 0
        while len(request.block_ids) > keep:
            self._release(request.block_ids.pop())
            released += 1
        return released

    def uncache_block(self, block_id: int) -> None:
        """Drop a block's cache entry (used by offload tier on invalidation)."""
        self.groups[0].uncache(block_id)

    # ---------- post-step caching ----------

    def cache_full_blocks(self, request: Request) -> None:
        """Register content hashes for the request's now-full blocks."""
        if not self.enable_prefix_caching:
            return
        hashes = self.request_block_hashes(request)
        n_full_computed = request.num_computed_tokens // self.block_size
        for i in range(min(n_full_computed, len(hashes), len(request.block_ids))):
            b = request.block_ids[i]
            if b in self._hash_of:
                continue
            h = hashes[i]
            if h in self._cached:
                continue        # another block already canonical for this hash
            self._hash_of[b] = h
            self._cached[h] = b
            for cb in self.on_block_stored:
                cb(h, b)
        if len(self.groups) > 1:
            ids, win = request.window_block_ids, self.groups[1]
            for i in range(request.window_first_block,
                           min(n_full_computed, len(hashes), len(ids))):
                if ids[i]:
                    win.store(ids[i], hashes[i])

    def lookup_hash(self, h: bytes) -> Optional[int]:
        return self._cached.get(h)
