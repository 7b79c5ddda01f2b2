"""Host-side KV page management: pool allocator + prefix cache + tiers.

Parity: vLLM's PagedAttention block manager / DeepSpeed-FastGen's blocked
KV cache, host-side only. The jitted serving step never sees this module
— it consumes the *result* (per-slot page-table int32 vectors, an
optional copy-on-write source vector and, tiered, the promotion staging
buffer) and keeps its ONE fixed shape. The only device-touching
functions here are :func:`export_pages` / :func:`import_pages`, the
eager page-payload transfer the fleet's prefill→decode KV handoff runs
BETWEEN steps (serving/fleet/handoff.py), and the spiller's demote
export.

- :class:`PagePool` — refcounted free-list over ``num_pages`` physical
  page ids. A page is *live* while any slot or prefix-cache entry holds a
  reference; ``free + live == num_pages`` is the leak invariant the
  scheduler asserts after every tick.
- :class:`PrefixCache` — chained-hash map from token prefixes to pages a
  finished request left behind. Full pages chain with
  ``crc32(block_bytes, prev_hash)``; the partial tail page is stored with
  its valid-token run. Matches verify actual token equality (hash
  collisions degrade to misses, never to wrong KV). Entries hold one pool
  reference each; LRU eviction under pool pressure DEMOTES full-chain
  entries to the host tier instead of dropping them (when a spiller is
  attached) — a fleet-wide shared system prompt survives HBM pressure.
- :class:`HostPageStore` — the second tier: codec-compressed page blobs
  in pinned-host buffers (the ``runtime/swap_tensor`` two-generation
  buffer-pool pattern), with an optional NVMe third tier through
  ``ops/aio`` behind the same put/get/drop interface.
- :class:`PageSpiller` — the engine↔host bridge: ``demote`` exports one
  physical page and codec-encodes it at rest (``comm/wires``: fp32 spill
  is bitwise, int8 within the codec's stated lane-wise bound); ``load``
  decodes one page back for the step's promotion staging buffer. WHICH
  pages move is the scheduler's decision; key lifecycle too.

Sharing is read-only: a slot whose write frontier lands inside a shared
page never writes it in place — the scheduler allocates a fresh page and
the step copies the shared page's KV into it before the chunk write
(copy-on-write, in-step, fixed shape).
"""

from __future__ import annotations

import zlib
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def chain_hash(prev: int, block) -> int:
    """Chained block hash: crc32 of the token block seeded by the previous
    link, so a page's key commits to the ENTIRE prefix before it (KV at a
    position depends on every earlier token)."""
    return zlib.crc32(np.asarray(block, np.int32).tobytes(), prev)


def chain_hashes(tokens, page_size: int) -> List[int]:
    """The chained hash of every FULL page-sized block of ``tokens``, in
    order. Because each link commits to the whole prefix before it, these
    keys are globally comparable: two caches (on two replicas) holding the
    same chain hash hold KV for the same token prefix — modulo crc32
    collisions, which every consumer must let degrade to misses (the
    router's index may mis-route on one; the replica's token-verified
    ``match`` then treats it as a miss, never as wrong KV)."""
    toks = np.asarray(tokens, np.int32).reshape(-1)
    ps = int(page_size)
    out: List[int] = []
    h = 0
    for i in range(toks.size // ps):
        h = chain_hash(h, toks[i * ps: (i + 1) * ps])
        out.append(h)
    return out


def longest_chain_walk(token_block_hashes, contains) -> int:
    """The ONE definition of "longest matching block chain": the length of
    the leading run of ``token_block_hashes`` for which ``contains(hash)``
    holds. Shared by :meth:`PrefixCache.longest_chain` (the replica-local
    cache view) and the fleet router's :class:`GlobalPrefixIndex` (the
    event-maintained cross-replica mirror), so routing and matching agree
    on what "longest chain" means. Accepts any iterable and consumes only
    up to the first miss — ``match`` feeds it a lazy hash generator, so a
    cold cache never pays for hashing a whole long prompt. Hash-presence
    only — callers that hand out KV must still verify token equality."""
    n = 0
    for h in token_block_hashes:
        if not contains(h):
            break
        n += 1
    return n


# ------------------------------------------------------- page payload I/O
def export_pages(cache: Dict[str, "object"], page_ids: Sequence[int]
                 ) -> Dict[str, "object"]:
    """Gather the payload of physical ``page_ids`` out of a paged KV pool
    (``init_paged_cache`` layout: the page axis is axis 1 of every leaf,
    scales included). Returns ``{leaf: [L, n_pages, ...]}`` device arrays
    — an immutable snapshot (the pool is updated functionally by the
    step, so later steps can never mutate an exported payload). This is
    the prefill half of the fleet's prefill→decode KV handoff: a page
    TRANSFER, not a tensor reshape."""
    import jax.numpy as jnp

    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    return {k: jnp.take(v, ids, axis=1) for k, v in cache.items()}


def check_page_payload(cache: Dict[str, "object"],
                       payload: Dict[str, "object"], n_pages: int) -> None:
    """Validate an :func:`export_pages` payload against a destination
    pool: every leaf present, ``n_pages`` wide, page geometry matching."""
    for k, v in cache.items():
        if k not in payload:
            raise KeyError(f"import_pages: payload missing leaf {k!r}")
        p = payload[k]
        if p.shape[1] != n_pages or p.shape[0] != v.shape[0] \
                or p.shape[2:] != v.shape[2:]:
            raise ValueError(
                f"import_pages: payload {k} shape {p.shape} does not fit "
                f"{n_pages} pages of a pool leaf shaped {v.shape}"
            )


def scatter_pages(cache: Dict[str, "object"],
                  payload: Dict[str, "object"],
                  ids) -> Dict[str, "object"]:
    """The traceable scatter core of :func:`import_pages`. The serving
    engine jits this with the pool DONATED (import_kv_pages), so a
    handoff updates the destination arena in place — O(pages moved), not
    an O(arena) copy per transfer."""
    return {
        k: v.at[:, ids].set(payload[k].astype(v.dtype))
        for k, v in cache.items()
    }


def import_pages(cache: Dict[str, "object"], payload: Dict[str, "object"],
                 dst_page_ids: Sequence[int]) -> Dict[str, "object"]:
    """Scatter an :func:`export_pages` payload into ``dst_page_ids`` of a
    (possibly different) pool with the same page geometry. Returns the new
    pool dict; the caller owns re-asserting device placement/sharding
    (ServingEngine.import_kv_pages does, so the jitted step's donated
    carry keeps the layout it compiled against). Host-side refcounts of
    the destination pages are the destination scheduler's business —
    the leak invariant ``free + live == num_pages`` must hold on BOTH
    pools after every transfer (asserted by the fleet handoff)."""
    import jax.numpy as jnp

    ids = np.asarray(dst_page_ids, np.int32)
    check_page_payload(cache, payload, ids.size)
    return scatter_pages(cache, payload, jnp.asarray(ids))


# --------------------------------------------- tiered host spill (ISSUE 18)
# staging-buffer width: pages promoted back per step. TWO slots — the
# PR-1 rotating double-buffer carry applied to the paged gather: slot A's
# page-in rides under the step consuming slot B, and the step's staged
# scatter runs BEFORE its gathers so a promoted page is attendable the
# same step it lands. Static: the stage arrays' shape is part of the ONE
# compiled program.
STAGE_SLOTS = 2


def encode_page(payload: Dict[str, "object"], codec
                ) -> Dict[str, Tuple[str, dict, Dict[str, np.ndarray]]]:
    """Codec-compress one single-page :func:`export_pages` payload at
    rest. Float leaves reshape to the wire codec's canonical ``[B, R, L]``
    operand (B = layers, L = the innermost lane axis) and encode; integer
    leaves (an int8-quantized pool's q arrays) are stored raw — they are
    already at storage width. The fp32 codec is the identity, so an fp32
    spill round-trips bitwise; int8 stays within the codec's stated
    lane-wise bound (``codec.bound``)."""
    import jax.numpy as jnp

    blob: Dict[str, Tuple[str, dict, Dict[str, np.ndarray]]] = {}
    for k, v in payload.items():
        arr = np.asarray(v)
        meta = {"shape": tuple(arr.shape), "dtype": str(arr.dtype)}
        if arr.dtype.kind == "f":
            x3 = jnp.asarray(arr, jnp.float32).reshape(
                arr.shape[0], -1, arr.shape[-1]
            )
            parts = {
                pk: np.ascontiguousarray(np.asarray(pv))
                for pk, pv in codec.encode(x3).items()
            }
            blob[k] = ("codec", meta, parts)
        else:
            blob[k] = ("raw", meta, {"x": np.ascontiguousarray(arr)})
    return blob


def decode_page(blob, codec) -> Dict[str, np.ndarray]:
    """Invert :func:`encode_page` back to the pool's leaf shapes/dtypes
    (numpy — the promotion staging buffer fills from this host-side)."""
    import jax.numpy as jnp

    out: Dict[str, np.ndarray] = {}
    for k, (mode, meta, parts) in blob.items():
        shape = tuple(meta["shape"])
        dt = np.dtype(meta["dtype"])
        if mode == "raw":
            out[k] = parts["x"]
            continue
        rows = 1
        for d in shape[1:-1]:
            rows *= d
        dec = codec.decode(
            {pk: jnp.asarray(pv) for pk, pv in parts.items()},
            rows, jnp.float32,
        )
        out[k] = np.asarray(dec).reshape(shape).astype(dt)
    return out


def blob_nbytes(blob) -> int:
    """At-rest bytes of one encoded page blob (what the host tier — and
    the ``kv_spill`` analytic stream — actually pays per page)."""
    return sum(
        int(p.nbytes)
        for _mode, _meta, parts in blob.values()
        for p in parts.values()
    )


class HostPageStore:
    """Tier 2 (+3): codec-compressed page blobs in pinned-host buffers,
    overflowing to NVMe through ``ops/aio`` when ``spill_dir`` is set.

    ``capacity_pages`` bounds the pinned-host tier (the
    ``serving.host_pages`` knob); the NVMe tier behind it is bounded only
    by disk. ``put`` returns an opaque int key, or None when every tier
    is full — in which case nothing was stored (the caller's demotion
    rolls back to the plain drop path). Buffers recycle through the
    :class:`runtime.swap_tensor.PinnedBufferPool` two-generation
    discipline: a dropped blob's buffers become reusable only after the
    NEXT drop generation retires, so a consumer still decoding the
    previous generation never sees them overwritten."""

    def __init__(self, capacity_pages: int, codec: str = "fp32",
                 spill_dir: Optional[str] = None,
                 buffer_count: int = 4 * STAGE_SLOTS):
        from ..comm.wires import get_codec
        from ..runtime.swap_tensor import PinnedBufferPool

        self.capacity = int(capacity_pages)
        self.codec = get_codec(codec)
        self.spill_dir = spill_dir
        self._blobs: Dict[int, dict] = {}   # key -> blob (pinned-host tier)
        self._disk: Dict[int, dict] = {}    # key -> file skeleton (NVMe)
        self._next_key = 0
        self._pool = PinnedBufferPool(buffer_count=buffer_count)
        self._aio = None
        self.bytes_resident = 0

    # ------------------------------------------------------------ tiers
    def _nvme(self):
        if self._aio is None:
            import os

            from ..ops.aio import AsyncIOHandle

            os.makedirs(self.spill_dir, exist_ok=True)
            self._aio = AsyncIOHandle(num_threads=2)
        return self._aio

    def _to_pinned(self, blob):
        """Copy a blob's parts into pooled host buffers (the arrays
        handed in may alias device buffers on a CPU client — the store
        must own its bytes)."""
        out = {}
        for k, (mode, meta, parts) in blob.items():
            pp = {}
            for pk, pv in parts.items():
                buf = self._pool.take(pv.shape, pv.dtype)
                np.copyto(buf, pv)
                pp[pk] = buf
            out[k] = (mode, meta, pp)
        return out

    def put(self, blob) -> Optional[int]:
        """Store one encoded page; returns its key, or None when full
        (host tier at capacity and no NVMe tier configured). On None
        NOTHING was stored — demotion failure is atomic."""
        if len(self._blobs) < self.capacity:
            stored = self._to_pinned(blob)
            key = self._next_key
            self._next_key += 1
            self._blobs[key] = stored
            self.bytes_resident += blob_nbytes(stored)
            return key
        if self.spill_dir is not None:
            return self._put_disk(blob)
        return None

    def _put_disk(self, blob) -> int:
        import os

        aio = self._nvme()
        key = self._next_key
        self._next_key += 1
        skel = {}
        reqs = []
        for k, (mode, meta, parts) in blob.items():
            pp = {}
            for pk, pv in parts.items():
                path = os.path.join(
                    self.spill_dir, f"page{key}.{k}.{pk}.bin"
                )
                arr = np.ascontiguousarray(pv)
                reqs.append((aio.submit_write(path, arr), arr))
                pp[pk] = (path, tuple(arr.shape), str(arr.dtype))
            skel[k] = (mode, meta, pp)
        for r, _buf in reqs:  # buffers stay referenced until the write lands
            aio.wait(r)
        self._disk[key] = skel
        return key

    def get(self, key: int):
        """The blob for ``key`` (reads the NVMe tier back into fresh host
        buffers when it overflowed there). Does NOT remove it."""
        blob = self._blobs.get(key)
        if blob is not None:
            return blob
        skel = self._disk.get(key)
        if skel is None:
            raise KeyError(f"HostPageStore: unknown page key {key}")
        aio = self._nvme()
        out = {}
        for k, (mode, meta, pp) in skel.items():
            parts = {}
            reqs = []
            for pk, (path, shape, dt) in pp.items():
                buf = np.empty(shape, np.dtype(dt))
                reqs.append(aio.submit_read(path, buf))
                parts[pk] = buf
            for r in reqs:
                aio.wait(r)
            out[k] = (mode, meta, parts)
        return out

    def drop(self, key: int) -> None:
        blob = self._blobs.pop(key, None)
        if blob is not None:
            self.bytes_resident -= blob_nbytes(blob)
            dropped = [
                p for _m, _meta, parts in blob.values()
                for p in parts.values()
            ]
            self._pool.retire_generation(dropped)
            return
        skel = self._disk.pop(key, None)
        if skel is not None:
            import os

            for _m, _meta, pp in skel.values():
                for path, _shape, _dt in pp.values():
                    try:
                        os.remove(path)
                    except FileNotFoundError:
                        pass
            return
        raise KeyError(f"HostPageStore: dropping unknown page key {key}")

    # ------------------------------------------------------- accounting
    def __contains__(self, key: int) -> bool:
        return key in self._blobs or key in self._disk

    def keys(self) -> List[int]:
        """Every resident key (host + NVMe tiers), SORTED — iteration
        over the store must be order-deterministic so state fingerprints
        (analysis/modelcheck) and counterexample replays are stable
        across runs."""
        return sorted(set(self._blobs) | set(self._disk))

    @property
    def host_count(self) -> int:
        return len(self._blobs)

    @property
    def disk_count(self) -> int:
        return len(self._disk)

    @property
    def resident_count(self) -> int:
        return len(self._blobs) + len(self._disk)

    def close(self) -> None:
        if self._aio is not None:
            self._aio.close()
            self._aio = None


class PageSpiller:
    """Demote/load bridge between the device pool and a HostPageStore.

    ``export_fn(page_ids) -> {leaf: [L, n, ...]}`` is late-bound to the
    engine's CURRENT pool arrays (functional updates: an export after
    step t reads exactly step t's settled content). Pure data movement —
    the scheduler decides which pages move and owns key lifecycle."""

    def __init__(self, store: HostPageStore, export_fn, metrics=None):
        self.store = store
        self._export = export_fn
        self.metrics = metrics
        self.pages_spilled = 0
        self.pages_loaded = 0

    def demote(self, page_id: int) -> Optional[int]:
        """Export + codec-encode one physical page into the store.
        Returns the store key, or None when the store is full — in which
        case nothing was mutated anywhere (put-before-free: the caller
        only releases the HBM page on success, so a mid-demotion failure
        rolls back to the plain drop path atomically)."""
        blob = encode_page(self._export([page_id]), self.store.codec)
        key = self.store.put(blob)
        if key is not None:
            self.pages_spilled += 1
            if self.metrics is not None:
                self.metrics.on_spill(blob_nbytes(blob))
        return key

    def load(self, key: int) -> Tuple[Dict[str, np.ndarray], int]:
        """Decode one stored page for the promotion staging buffer:
        ``({leaf: [L, 1, ...]} numpy in pool dtypes, at-rest bytes)``."""
        blob = self.store.get(key)
        self.pages_loaded += 1
        return decode_page(blob, self.store.codec), blob_nbytes(blob)

    def drop(self, key: int) -> None:
        self.store.drop(key)


class PagePool:
    """Refcounted physical-page allocator (host side, O(1) ops)."""

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"PagePool needs >= 1 page, got {num_pages}")
        self.num_pages = int(num_pages)
        self.refcount = np.zeros(self.num_pages, np.int64)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))

    def alloc(self) -> Optional[int]:
        """One fresh page with refcount 1, or None when exhausted."""
        if not self._free:
            return None
        page = self._free.pop()
        self.refcount[page] = 1
        return page

    def incref(self, page: int) -> None:
        if self.refcount[page] <= 0:
            raise AssertionError(f"incref on dead page {page}")
        self.refcount[page] += 1

    def decref(self, page: int) -> None:
        if self.refcount[page] <= 0:
            raise AssertionError(f"decref on dead page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self._free.append(page)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return int((self.refcount > 0).sum())

    def check_leaks(self, held: Optional[Iterable[int]] = None) -> None:
        """The leak invariant: ``free + live == num_pages``, and (when the
        caller supplies its own view) the pool's refcounts match the
        references the scheduler believes exist, page for page. ``held``
        is that view: the page ids the holders name, one a reference. The
        scheduler audits every tick, so they are counted in numpy (a dict
        cost a step's host time in proportion to the pages alive)."""
        if self.free_count + self.live_count != self.num_pages:
            raise AssertionError(
                f"page leak: free {self.free_count} + live "
                f"{self.live_count} != num_pages {self.num_pages}"
            )
        if held is None:
            return
        counts = np.bincount(np.fromiter(held, np.int64),
                             minlength=self.num_pages)
        if not np.array_equal(counts, self.refcount):
            mine, theirs = (
                {int(p): int(c[p]) for p in np.nonzero(c)[0]}
                for c in (self.refcount, counts)
            )
            raise AssertionError(
                f"page refcount drift: pool {mine} != holders {theirs}"
            )


class PrefixCache:
    """Token-prefix → shared KV pages, refcounted through a PagePool.

    Full pages key on the chain hash of all tokens up to and including the
    page; the partial tail keys on (chain hash so far, tail token run).
    ``match`` walks a prompt greedily and returns the shared pages plus
    how many tokens they cover; the caller caps the hit (a request must
    always feed at least its final prompt token to sample) and increfs.
    """

    def __init__(self, pool: PagePool, page_size: int, spiller=None):
        self.pool = pool
        self.page_size = int(page_size)
        # full pages: chain_hash -> (page, block_tuple); tails:
        # chain_hash -> [(tail_tuple, page), ...]. One LRU order over both
        # (key -> ("full"|"tail", chain_hash, page, tokens_tuple)).
        self._full: "OrderedDict[int, Tuple[int, Tuple[int, ...]]]" = (
            OrderedDict()
        )
        self._tails: Dict[int, List[Tuple[Tuple[int, ...], int]]] = {}
        self._lru: "OrderedDict[Tuple, None]" = OrderedDict()
        # cache-event listener: ``listener(event, kind, chain_hash, page)``
        # with event in {"insert", "evict"} and kind in {"full", "tail",
        # "host"}. The fleet router's GlobalPrefixIndex subscribes here to
        # mirror each replica's full-page chain keys (HBM- and host-tier)
        # without polling; None (the default) is the zero-overhead
        # single-engine path.
        self.listener = None
        # ---- host tier (ISSUE 18): evicted FULL chains demote to the
        # spiller's HostPageStore instead of dropping. chain_hash ->
        # (store_key, block); its own LRU; pins protect keys whose
        # promotion a slot is waiting on from host-tier eviction.
        self.spiller = spiller
        self._host_full: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        self._host_lru: "OrderedDict[int, None]" = OrderedDict()
        self._host_pins: Dict[int, int] = {}

    def _emit(self, event: str, kind: str, h: int, page: int) -> None:
        if self.listener is not None:
            self.listener(event, kind, h, page)

    def __len__(self) -> int:
        return len(self._lru)

    @property
    def held_pages(self) -> List[int]:
        return [key[2] for key in self._lru]

    # ---------------------------------------------------------------- match
    def longest_chain(self, token_block_hashes) -> int:
        """Public longest-matching-block-chain lookup: how many leading
        chained-crc32 FULL-page keys (:func:`chain_hashes`, or any lazy
        iterable of them — only the matched prefix is ever consumed) this
        cache holds. Hash-presence only — a crc32 collision can overstate
        the depth, which is exactly why :meth:`match` re-verifies token
        equality before handing out pages (collisions degrade to misses,
        never to wrong KV). Used by the scheduler's match path and by the
        fleet router's global index (the same :func:`longest_chain_walk`
        over its event-maintained per-replica mirror)."""
        return longest_chain_walk(token_block_hashes,
                                  self._full.__contains__)

    def match(self, prompt: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of ``prompt``: (pages, covered_tokens).
        Pages are NOT incref'd — the caller takes references for the ones
        it keeps. The hash walk is :meth:`longest_chain` over a LAZY
        chain-hash generator (a miss at block i stops hashing — a cold
        cache costs one crc32, not one per prompt page); token equality
        is then verified block-for-block (hash collisions shrink the
        match — a miss, never wrong KV)."""
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        ps = self.page_size
        hashes: List[int] = []

        def lazy_hashes():
            h = 0
            for i in range(len(toks) // ps):
                h = chain_hash(h, toks[i * ps: (i + 1) * ps])
                hashes.append(h)
                yield h

        depth = self.longest_chain(lazy_hashes())
        pages: List[int] = []
        covered = 0
        h = 0
        for i in range(depth):
            block = tuple(toks[covered: covered + ps])
            nh = hashes[i]
            entry = self._full[nh]
            if entry[1] != block:
                break  # crc32 collision: stop the walk — a miss
            pages.append(entry[0])
            self._lru.move_to_end(("full", nh, entry[0], block))
            covered += ps
            h = nh
        # partial tail: use the stored run's leading tokens that match the
        # remaining prompt (KV beyond the match is never attendable — the
        # joining slot's frontier stops at the match)
        rest = toks[covered:]
        best: Tuple[int, Tuple[Tuple[int, ...], int]] = (0, None)
        for tail, page in self._tails.get(h, ()):
            n = 0
            for a, b in zip(tail, rest):
                if a != b:
                    break
                n += 1
            if n > best[0]:
                best = (n, (tail, page))
        if best[0] > 0:
            tail, page = best[1]
            pages.append(page)
            self._lru.move_to_end(("tail", h, page, tail))
            covered += best[0]
        return pages, covered

    # --------------------------------------------------------------- insert
    def insert(self, tokens: Sequence[int], pages: Sequence[int]) -> int:
        """Publish a finished request's pages for reuse. ``tokens`` is the
        run whose KV the pages hold (prompt + generated-but-last);
        ``pages`` the physical pages covering it in order. Each entry the
        cache keeps takes ONE pool reference; duplicates of existing
        entries are skipped (the caller's own references are its business).
        Returns the number of entries inserted."""
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        ps = self.page_size
        inserted = 0
        h = 0
        full = len(toks) // ps
        for i in range(full):
            block = tuple(toks[i * ps: (i + 1) * ps])
            nh = chain_hash(h, block)
            if nh not in self._full:
                self._full[nh] = (int(pages[i]), block)
                self._lru[("full", nh, int(pages[i]), block)] = None
                self.pool.incref(int(pages[i]))
                self._emit("insert", "full", nh, int(pages[i]))
                inserted += 1
            # ALSO register the full page's run for partial matching: a
            # prompt diverging mid-page (the shared-system-prompt shape)
            # still shares this page's leading tokens, copy-on-write at
            # the divergence point
            inserted += self._add_tail(h, block, int(pages[i]))
            h = nh
        tail = tuple(toks[full * ps:])
        if tail and full < len(pages):
            inserted += self._add_tail(h, tail, int(pages[full]))
        return inserted

    def _add_tail(self, h: int, run: Tuple[int, ...], page: int) -> int:
        runs = self._tails.setdefault(h, [])
        if any(existing == run for existing, _ in runs):
            return 0
        runs.append((run, page))
        self._lru[("tail", h, page, run)] = None
        self.pool.incref(page)
        self._emit("insert", "tail", h, page)
        return 1

    # --------------------------------------------------------------- evict
    def evict_lru(self) -> bool:
        """Evict the least-recently-used entry (its pool reference with
        it). With a spiller attached, FULL chain entries DEMOTE to the
        host tier (codec-compressed at rest) instead of vanishing — a
        later match promotes them back; tails and collisions still drop.
        Returns False when the cache is empty."""
        if not self._lru:
            return False
        key, _ = self._lru.popitem(last=False)
        kind, h, page, toks = key
        if kind == "full":
            self._full.pop(h, None)
            if self.spiller is not None and h not in self._host_full:
                self._demote_full(h, page, toks)
        else:
            runs = self._tails.get(h, [])
            self._tails[h] = [r for r in runs if r != (toks, page)]
            if not self._tails[h]:
                del self._tails[h]
        self.pool.decref(page)
        self._emit("evict", kind, h, page)
        return True

    # ----------------------------------------------------------- host tier
    def _demote_full(self, h: int, page: int,
                     block: Tuple[int, ...]) -> Optional[int]:
        """Demote one evicted full page to the host tier. On a full
        store, unpinned host-LRU chains make room first; a still-full
        store falls back to the plain drop (demotion failure is atomic —
        :meth:`PageSpiller.demote` mutates nothing on None)."""
        skey = self.spiller.demote(page)
        while skey is None and self._evict_host_lru():
            skey = self.spiller.demote(page)
        if skey is not None:
            self._host_full[h] = (skey, block)
            self._host_lru[h] = None
            self._emit("insert", "host", h, -1)
        return skey

    def _evict_host_lru(self) -> bool:
        """Drop the oldest UNPINNED host-tier chain (pinned keys have a
        slot's promotion in flight — never yank those)."""
        for h in list(self._host_lru):
            skey, _block = self._host_full[h]
            if self._host_pins.get(skey, 0) == 0:
                del self._host_lru[h]
                del self._host_full[h]
                self.spiller.drop(skey)
                self._emit("evict", "host", h, -1)
                return True
        return False

    def host_chain(self, tokens: Sequence[int], start: int,
                   max_pages: int) -> List[Tuple[int, int]]:
        """Continue a chain walk into the host tier: from page-aligned
        token offset ``start``, the leading run of full blocks whose
        chained hash has a host-resident entry — token-verified, like
        :meth:`match` (collisions degrade to misses). Returns
        ``[(store_key, chain_hash)]`` per matched block; the caller pins
        each key (:meth:`pin_host`) until its promotion lands."""
        if self.spiller is None or start % self.page_size != 0:
            return []
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        ps = self.page_size
        h = 0
        for i in range(start // ps):
            h = chain_hash(h, toks[i * ps: (i + 1) * ps])
        out: List[Tuple[int, int]] = []
        pos = start
        while len(out) < max_pages and pos + ps <= len(toks):
            block = tuple(toks[pos: pos + ps])
            nh = chain_hash(h, block)
            ent = self._host_full.get(nh)
            if ent is None or ent[1] != block:
                break
            out.append((ent[0], nh))
            self._host_lru.move_to_end(nh)
            h = nh
            pos += ps
        return out

    def pin_host(self, key: int) -> None:
        self._host_pins[key] = self._host_pins.get(key, 0) + 1

    def unpin_host(self, key: int) -> None:
        n = self._host_pins.get(key, 0) - 1
        if n <= 0:
            self._host_pins.pop(key, None)
        else:
            self._host_pins[key] = n

    @property
    def host_keys(self) -> List[int]:
        return [skey for skey, _block in self._host_full.values()]

    @property
    def host_entries(self) -> int:
        return len(self._host_full)

    def clear(self) -> None:
        while self.evict_lru():
            pass
        # the LRU drain above DEMOTES full chains when tiered — now drop
        # the host tier too (pins should be empty at clear time; a pinned
        # key here is a scheduler lifecycle bug surfaced by the store)
        for h in list(self._host_lru):
            skey, _block = self._host_full.pop(h)
            del self._host_lru[h]
            self.spiller.drop(skey)
            self._emit("evict", "host", h, -1)
