"""Radix prefix cache over the paged KV pool (RadixAttention-style).

Millions of requests open with the same system prompt / few-shot
template; every admission used to re-prefill and re-store those rows.
This cache turns cross-request prefix reuse into an admission-time
lookup: a radix tree keyed on PAGE-GRANULARITY token-id chunks, where
each node owns one physical KV page whose ``page_size`` rows hold
exactly the KV of that chunk, computed once by whichever stream got
there first.

Custody is refcounts, not copies (models/batch_engine.PageAllocator):

* ``insert`` adopts a completed prompt's fully-populated pages — the
  cache takes ONE allocator reference per new node, so the pages
  outlive the stream that computed them.
* ``lookup`` walks the longest cached page-aligned prefix of a new
  prompt; the engine refs those pages into the new stream's block
  table and starts prefill at the divergence point. Shared pages are
  immutable: chunk prefill and decode only ever write rows past the
  shared prefix, which land in the stream's own fresh pages (the
  copy-on-write boundary page is re-materialized by the divergence
  chunk, never written in place — no kernel changes).
* ``evict`` drops unpinned, unshared pages LRU-leaf-first when the
  pool is under admission pressure. Eviction yields to admission —
  cached pages are a bonus, never a reason to shed — and a page still
  shared with a live stream (refcount > 1) is in active use, so it is
  never evicted out from under the stream; dropping the cache's
  reference to it would not free a page anyway.
* ``pin``/``unpin`` protect a preempted victim's prefix path from
  eviction while it waits to resume (refcount custody, not slot
  custody): resume re-prefills only the unshared tail.

Token ids are exact-match keys (no hashing, no collisions): two
prompts share a node only when their page-size chunk of token ids is
identical, which is the greedy-exactness contract.

The fleet plane additionally needs a *bounded, shippable* summary of
what this cache holds, so a router can longest-prefix-match a prompt
against remote replicas without shipping the tree. Every node carries a
cumulative **hash chain** — ``blake2b(parent_chain || chunk token ids)``
with the root seeded from the adapter identity, computed once at insert
time (incremental, never re-walked) — and ``digest`` exports the top-N
most-recently-used paths as ``(chain, token_len, pages)`` tuples. The
hash is deterministic across processes (never Python's salted builtin
``hash``), so a router hashing a prompt with ``prompt_hash_chain``
produces byte-identical chains to compare against any replica's digest.
Within the tree itself, hashing plays no role in correctness: matching
stays exact on token ids.

Multi-tenant LoRA serving adds an ``adapter`` dimension to that
contract: the KV a stream computes depends on its adapter's weights,
so two tenants with byte-identical prompts must NEVER share pages.
The cache therefore keys every path on ``(adapter, tokens)`` — one
radix root per adapter identity (the stable tenant NAME, not the
resident slot index, which is recycled by eviction) — and eviction /
accounting walk all roots.

**State snapshots, the second kind of thing a node can hold.** A model
whose streams keep a per-slot state beside their pages (a delta-rule
layer's state and convolution tail) cannot start a prompt at a cached
depth with the pages alone: it needs the state as it stood after that
depth's last row. The engine keeps a pool of ``snapshots`` rows shaped
like one slot's state (``PagedBatchEngine``, ``state_snapshots``) and
this cache keeps the custody of its rows, as it keeps the pages': a
node may hold one row (``snap``), the engine grants a prefix only down
to a node that holds one, a row leaves with its node (``evict``), and a
full pool gives up the least recently used row that no admission was
promised (``snapshot_take``; a promise is ``snap_pins``, held from the
grant to the copy into the slot). A node left without a snapshot at or
below it is of no use to such an engine and goes with the row.
"""

from __future__ import annotations

import hashlib
import heapq
import itertools


def _root_chain(adapter: str | None) -> str:
    """Chain seed for an adapter's radix root. Seeding from the tenant
    identity means two tenants' byte-identical prompts hash to different
    chains — the digest inherits the cache's isolation contract."""
    h = hashlib.blake2b(b"dora-prefix-root:", digest_size=8)
    h.update((adapter or "").encode())
    return h.hexdigest()


def _chain_hash(parent_chain: str, key) -> str:
    """One incremental chain step: hash the parent's cumulative chain
    plus this chunk's token ids. Deterministic across processes."""
    h = hashlib.blake2b(digest_size=8)
    h.update(parent_chain.encode())
    for t in key:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.hexdigest()


def prompt_hash_chain(ids, page_size: int, adapter: str | None = None):
    """Cumulative page-boundary chain of a prompt: one ``(chain,
    token_len)`` pair per full page-size chunk, byte-identical to the
    chains a replica's cache computes at insert. The router side of the
    digest contract — see ``PrefixCache.digest``."""
    chain = _root_chain(adapter)
    out: list[tuple[str, int]] = []
    ps = page_size
    for i in range(0, (len(ids) // ps) * ps, ps):
        chain = _chain_hash(chain, tuple(ids[i : i + ps]))
        out.append((chain, i + ps))
    return out


class _Node:
    __slots__ = (
        "key", "page", "children", "parent", "last_used", "pins", "chain",
        "snap", "snap_used", "snap_pins",
    )

    def __init__(self, key: tuple, page: int | None, parent: "_Node | None",
                 chain: str = ""):
        self.key = key          # edge label: page_size token ids
        self.page = page        # physical page id (None only at root)
        self.children: dict[tuple, _Node] = {}
        self.parent = parent
        self.last_used = 0
        self.pins = 0
        self.chain = chain      # cumulative blake2b chain root..here
        self.snap: int | None = None  # row of the engine's snapshot pool
        self.snap_used = 0      # LRU stamp of the snapshot's last grant
        self.snap_pins = 0      # grants whose copy into a slot is to come


class PrefixCache:
    """See module docstring. One instance per PagedBatchEngine; all
    methods run on the scheduler thread (no locking)."""

    def __init__(self, allocator, page_size: int, *, max_pages: int = 0,
                 snapshots: int = 0):
        self.allocator = allocator
        self.page_size = page_size
        #: optional hard cap on cached pages (0 = bounded only by pool
        #: pressure); insert evicts LRU past it
        self.max_pages = max_pages
        self._root = _Node((), None, None, _root_chain(None))
        #: adapter identity -> radix root; None/"" is the base tenant.
        #: Tenant isolation lives here: lookups only ever walk their
        #: own adapter's tree, so cross-tenant hits are structurally
        #: impossible.
        self._roots: dict[str | None, _Node] = {None: self._root}
        self._clock = itertools.count(1)
        #: pages (== nodes) currently held by the cache
        self.size = 0
        # -- accounting (cumulative; surfaced via ServingMetrics) --
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.inserted_pages = 0
        self.evicted_pages = 0
        #: ``evict`` calls that freed a page, and the nodes they looked
        #: at (walked or popped): visits a page freed is what eviction
        #: costs the admission that asked for it
        self.evict_calls = 0
        self.evict_visits = 0
        #: boundary pages re-materialized privately because the
        #: divergence point fell inside a cached page (mid-page
        #: divergence, or a fully-cached prompt re-running its final
        #: page to produce the first token)
        self.cow_copies = 0
        #: rows of the engine's snapshot pool (0 = the engine keeps
        #: none): the free ones, and the life of the others in counts
        self.snapshots = snapshots
        self._snap_free = list(range(snapshots - 1, -1, -1))
        self.snapshots_attached = 0
        self.snapshots_evicted = 0

    def _chunks(self, ids) -> list[tuple]:
        ps = self.page_size
        return [
            tuple(ids[i : i + ps])
            for i in range(0, (len(ids) // ps) * ps, ps)
        ]

    def _root_for(self, adapter: str | None, create: bool = False) -> _Node:
        root = self._roots.get(adapter or None)
        if root is None:
            root = _Node((), None, None, _root_chain(adapter or None))
            if create:
                self._roots[adapter or None] = root
        return root

    # -- lookup / insert -----------------------------------------------------

    def lookup(
        self, ids, adapter: str | None = None
    ) -> tuple[int, list[int], bool]:
        """Longest cached page-aligned prefix of ``ids``.

        Returns ``(matched_tokens, pages, mid_page)``: the matched
        length (a multiple of ``page_size``), the cached page ids in
        prefix order, and whether the divergence falls INSIDE the next
        cached page (some cached edge shares a proper prefix with the
        next chunk — the copy-on-write boundary case). Touches the
        matched path's LRU stamps; hit/miss accounting is the
        engine's, made against the prefix length it actually maps.
        ``adapter`` scopes the walk to that tenant's tree."""
        now = next(self._clock)
        node = self._root_for(adapter)
        pages: list[int] = []
        for key in self._chunks(ids):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = now
            pages.append(child.page)
            node = child
        matched = len(pages) * self.page_size
        tail = tuple(ids[matched : matched + self.page_size])
        mid_page = bool(tail) and any(
            k[0] == tail[0] for k in node.children
        )
        return matched, pages, mid_page

    def insert(self, ids, pages: list[int], adapter: str | None = None) -> int:
        """Adopt a completed prompt's fully-populated pages: one node
        per page-size chunk of ``ids``, each new node taking one
        allocator reference on its page. Existing nodes keep their
        page (first writer wins — the duplicate page stays private to
        its stream and frees with it). Returns pages adopted.
        ``adapter`` scopes adoption to that tenant's tree."""
        now = next(self._clock)
        node = self._root_for(adapter, create=True)
        new = 0
        for key, page in zip(self._chunks(ids), pages):
            child = node.children.get(key)
            if child is None:
                child = _Node(key, page, node, _chain_hash(node.chain, key))
                node.children[key] = child
                self.allocator.ref([page])
                self.size += 1
                new += 1
            child.last_used = now
            node = child
        self.inserted_pages += new
        if self.max_pages and self.size > self.max_pages:
            self.evict(self.size - self.max_pages)
        return new

    # -- pin / unpin (preempted victims) -------------------------------------

    def pin(self, ids, adapter: str | None = None) -> int:
        """Pin the cached path matching ``ids`` against eviction (one
        pin per node; nestable). Returns the pinned token length."""
        node = self._root_for(adapter)
        n = 0
        for key in self._chunks(ids):
            child = node.children.get(key)
            if child is None:
                break
            child.pins += 1
            n += self.page_size
            node = child
        return n

    def unpin(self, ids, adapter: str | None = None) -> None:
        """Release one pin along the matching path (tolerates a path
        shorter than at pin time — impossible while pinned, but unpin
        must never raise on teardown)."""
        node = self._root_for(adapter)
        for key in self._chunks(ids):
            child = node.children.get(key)
            if child is None:
                break
            if child.pins > 0:
                child.pins -= 1
            node = child

    # -- eviction (pool pressure) --------------------------------------------

    def evictable_pages(self) -> int:
        """Pages eviction could return to the free list RIGHT NOW:
        nodes that are unpinned, unshared (refcount 1 — only the cache
        holds them), and whose whole subtree is likewise evictable (a
        pinned or in-use descendant keeps its ancestors reachable).
        Admission counts these as free-in-waiting."""

        # Post-order over an explicit stack: a 16k-token prompt is a
        # chain 1,024 pages deep, past Python's recursion limit.
        total = 0
        for root in set(self._roots.values()):
            blocked: set[_Node] = set()  # a descendant of these stays
            stack = [(c, False) for c in root.children.values()]
            while stack:
                n, seen = stack.pop()
                if not seen:
                    stack.append((n, True))
                    stack.extend((c, False) for c in n.children.values())
                    continue
                ok = (
                    n not in blocked
                    and n.pins == 0
                    and self.allocator.refcount(n.page) == 1
                )
                if ok:
                    total += 1
                elif n.parent is not None:
                    blocked.add(n.parent)
        return total

    def evict(self, need: int) -> int:
        """Free up to ``need`` pages, least-recently-used leaves first
        (a parent becomes a leaf once its children are gone, so cold
        branches unwind bottom-up). Skips pinned nodes and pages still
        shared with live streams. Returns pages actually freed.

        ONE walk finds every leaf that may go and puts it on a heap by
        its stamp; a pop drops the oldest and, where that leaves its
        parent a leaf that may go too, pushes the parent: nodes + need x
        log nodes, where a walk a page was need x nodes (0.7 s for 600
        pages of 8,000 under a full pool). The pages and their order
        are those of the walk a page. Ties: the stamp, then the place
        in the walk (``_nodes``' order, which a dropped leaf does not
        change for the nodes that stay). Stamps come from one clock and
        a touch stamps one path from its root, so in a tree that only
        ``lookup`` and ``insert`` stamped no two leaves tie. Nothing is
        kept between calls: refcounts change outside the cache."""
        if need <= 0:
            return 0
        refcount = self.allocator.refcount

        def may_go(n: _Node) -> bool:
            return not n.children and not n.pins and refcount(n.page) == 1

        place: dict[_Node, int] = {}
        heap = []
        for at, n in enumerate(self._nodes()):
            place[n] = at
            if may_go(n):
                heap.append((n.last_used, at, n))
        heapq.heapify(heap)
        freed = 0
        while heap and freed < need:
            node = heapq.heappop(heap)[2]
            parent = node.parent
            self._drop(node)
            freed += 1
            if parent.parent is not None and may_go(parent):
                heapq.heappush(heap, (parent.last_used, place[parent], parent))
        self.evicted_pages += freed
        self.evict_calls += bool(freed)
        self.evict_visits += len(place) + freed
        return freed

    def _drop(self, node: _Node) -> None:
        """Take a leaf out of the tree: its page's reference, and its
        snapshot row with it."""
        del node.parent.children[node.key]
        self.allocator.unref([node.page])
        self.size -= 1
        if node.snap is not None:
            self._snap_free.append(node.snap)
            node.snap = None
            self.snapshots_evicted += 1

    # -- state snapshots (a slot-state engine's second cache kind) -----------

    def _node_at(self, ids, depth: int, adapter: str | None) -> _Node | None:
        node = self._root_for(adapter)
        for key in self._chunks(ids[:depth]):
            node = node.children.get(key)
            if node is None:
                return None
        return node if node.page is not None else None

    def snapshots_on_path(self, ids, adapter: str | None = None,
                          limit: int | None = None) -> list[tuple[int, _Node]]:
        """``(depth, node)`` of every node on the cached path of ``ids``
        that holds a snapshot, shallowest first, down to ``limit``
        tokens. Touches no stamp."""
        node, found, depth = self._root_for(adapter), [], 0
        for key in self._chunks(ids if limit is None else ids[:limit]):
            node = node.children.get(key)
            if node is None:
                break
            depth += self.page_size
            if node.snap is not None:
                found.append((depth, node))
        return found

    def snapshot_promise(self, node: _Node) -> None:
        """A grant ends at ``node``: its row stays until the engine has
        copied it into the slot (:meth:`snapshot_release`)."""
        node.snap_pins += 1
        node.snap_used = next(self._clock)

    def snapshot_release(self, node: _Node) -> None:
        if node.snap_pins > 0:
            node.snap_pins -= 1

    def snapshot_take(self) -> int | None:
        """A row for a new snapshot: a free one, else the least recently
        granted one that no admission in flight was promised, taken from
        its node (and the node's now useless pages with it, as far up as
        nothing else hangs on them). None when every row is promised or
        on its way to a node."""
        if self._snap_free:
            return self._snap_free.pop()
        best = min((n for n in self._nodes()
                    if n.snap is not None and not n.snap_pins),
                   key=lambda n: n.snap_used, default=None)
        if best is None:
            return None
        row, best.snap = best.snap, None
        self.snapshots_evicted += 1
        while (best.parent is not None and not best.children
               and best.snap is None and not best.pins
               and self.allocator.refcount(best.page) == 1):
            parent = best.parent
            self._drop(best)
            self.evicted_pages += 1
            best = parent
        return row

    def snapshot_give_back(self, row: int) -> None:
        """A row that never reached a node (its stream went first)."""
        self._snap_free.append(row)

    def snapshot_attach(self, ids, depth: int, row: int,
                        adapter: str | None = None) -> bool:
        """Hand ``row`` to the node ``depth`` tokens down the path of
        ``ids``. False (and the row is the caller's still) where there is
        no such node or it holds a snapshot already: first writer wins,
        as with pages."""
        node = self._node_at(ids, depth, adapter)
        if node is None or node.snap is not None:
            return False
        node.snap = row
        node.snap_used = next(self._clock)
        self.snapshots_attached += 1
        return True

    def _nodes(self):
        """Every node of every tenant's tree, parents before children."""
        stack = [c for root in self._roots.values()
                 for c in root.children.values()]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    def snapshot_rows(self):
        """Iterate the rows that nodes hold (invariant checks)."""
        return (n.snap for n in self._nodes() if n.snap is not None)

    @property
    def snapshots_free(self) -> int:
        return len(self._snap_free)

    @property
    def snapshots_held(self) -> int:
        """Rows on nodes: every one attached and not yet taken back."""
        return self.snapshots_attached - self.snapshots_evicted

    def flush(self) -> int:
        """Evict everything evictable (tests / shutdown)."""
        return self.evict(self.size)

    # -- introspection -------------------------------------------------------

    def pages(self):
        """Iterate every cached page id across all tenants (invariant
        checks)."""
        stack = [
            c
            for root in self._roots.values()
            for c in root.children.values()
        ]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n.page

    def digest(self, top_n: int = 32) -> list[tuple[str, int, int]]:
        """Bounded fleet digest: the top-``top_n`` most-recently-used
        cached prefixes across all tenants, each as ``(chain,
        token_len, pages)``. Chains were computed incrementally at
        insert, so this is a walk plus a sort — no hashing here. A
        router matches a prompt by comparing ``prompt_hash_chain``
        output against these tuples (longest equal chain wins)."""
        entries: list[tuple[int, str, int]] = []
        stack = [
            (c, 1)
            for root in self._roots.values()
            for c in root.children.values()
        ]
        while stack:
            n, depth = stack.pop()
            stack.extend((c, depth + 1) for c in n.children.values())
            entries.append((n.last_used, n.chain, depth))
        entries.sort(reverse=True)
        return [
            (chain, depth * self.page_size, depth)
            for _, chain, depth in entries[:top_n]
        ]

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 4) if total else None,
            "hit_tokens": self.hit_tokens,
            "cached_pages": self.size,
            "inserted_pages": self.inserted_pages,
            "evicted_pages": self.evicted_pages,
            "cow_copies": self.cow_copies,
        }
