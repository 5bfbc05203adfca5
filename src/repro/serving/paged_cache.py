"""Block-paged KV cache: a shared page pool plus per-slot page tables.

Instead of one contiguous ``(B, Hkv, S, D)`` buffer per sequence bucket,
decode state lives in a single pool of fixed-size pages

    K, V : (num_layers, num_pages, Hkv, page_size, head_dim)

with a host-side free-list allocator and an int32 page table
``(nslots, table_blocks)`` mapping each slot's *logical* KV block to the
page that holds it.  ``page_size == block_size``, so the DecodePlan's
block-index tables translate to page indices by a single table lookup —
sparse block tables and page tables are the same table, and a head's
keep-set is just its set of resident pages.

Conventions:

* **Page 0 is the reserved null page.**  It is never allocated and stays
  zero; unused page-table entries point at it.  Validity masks and plan
  keep-bits already exclude unwritten positions, so the null page (and
  any stale bits in recycled pages) contribute exactly zero.
* Per-slot allocation is ``(bucket + decode_extra) // page_size`` pages,
  where ``bucket`` is the request's *former* sequence bucket — slots of
  different buckets coexist in one decode batch because shape-wise the
  batch is just ``(nslots, table_blocks)`` table rows.
* Prefill KV is written page-at-a-time (whole-cache or layer-at-a-time
  for chunked prefill); the decode append rewrites each slot's current
  page (its ``(Hkv, head_dim)`` token sliver set inside it) in the
  donated pool, retiring the ``grow_cache`` reallocation and whole-row
  ``cache_insert`` copies.
* **Pages are refcounted.**  :meth:`PageAllocator.acquire` grants fresh
  pages at refcount 1; :meth:`PageAllocator.share` takes an extra
  reference on already-allocated pages (the prefix-sharing path: a
  prompt-cache hit maps a donor's pages read-only, and the prefix index
  itself pins published runs); :meth:`PageAllocator.release` drops one
  reference and recycles the page onto the free list only at refcount 0.
  A page with refcount > 1 is *shared* and must never be written —
  writers copy-on-write first (:func:`copy_page`; the scheduler's
  ``_cow_append_page`` rewrites the table entry at the decode boundary).
  ``alloc``/``free`` remain as aliases of acquire/release for the
  single-owner call sites.
* **Release is atomic and guarded.**  The whole id list is validated
  *before* any mutation — out-of-range ids and over-releases (a double
  free, or more releases than references in one call) raise the typed
  :class:`PageAllocatorError` and leave the allocator untouched, so a
  bad id mid-list can never strand earlier ids half-freed, and a page
  can never be pushed onto the free list twice (the silent KV-aliasing
  bug where one page is later granted to two slots).
  :meth:`PageAllocator.check_consistency` audits the free-list/refcount
  partition; the test suite runs it after every scheduler-path test.
* The GQA pool covers the scanned transformer stack (dense/vlm/moe with
  GQA caches).  An MLA pool is one latent pool
  ``(num_layers, num_pages, 1, page_size, latent_dim)`` over every layer,
  the dense prefix layers first: each token keeps one ``c_kv ‖ k_rope``
  row per layer, no kv-head axis and no separate V.  Its prefill rows are
  written by a jitted insert that takes the pool donated, so the pool is
  never held twice.
"""
from __future__ import annotations

import functools
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard
from repro.kernels.decode_attn import gather_pages  # re-export  # noqa: F401
from repro.serving.cache_ops import slice_segment

NULL_PAGE = 0


class PageAllocatorError(ValueError):
    """Typed allocator-misuse error: releasing or sharing a page the
    allocator does not consider allocated (double free / free-list
    corruption) or an out-of-range id.  Raised *before* any mutation —
    the allocator state is unchanged when this propagates."""


class PageAllocator:
    """Refcounted host-side free-list over a shared page pool (page 0
    reserved).  ``acquire`` grants fresh pages at refcount 1, ``share``
    adds references, ``release`` drops them and recycles at zero."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least one allocatable page "
                             "(page 0 is the reserved null page)")
        self.num_pages = num_pages
        # pop() hands out ascending ids — deterministic and easy to read
        # in page-table dumps.
        self._free = list(range(num_pages - 1, 0, -1))
        # per-page reference count; 0 = free (or the null page).  The
        # refcount column doubles as the allocated-set: releasing a page
        # whose count is 0 is a double free, not a state change.
        self._refs = np.zeros((num_pages,), np.int32)
        self.peak_in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def refcount(self, page) -> int:
        """References held on ``page`` (0 = free).  Refcount > 1 means
        shared: the page is read-only and writers must COW first."""
        return int(self._refs[int(page)])

    def acquire(self, n: int) -> Optional[np.ndarray]:
        """n fresh page ids at refcount 1, or None if the pool lacks
        headroom (caller keeps the request WAITING — never a partial
        grant)."""
        if n > len(self._free):
            return None
        ids = np.asarray([self._free.pop() for _ in range(n)], np.int32)
        self._refs[ids] = 1
        self.peak_in_use = max(self.peak_in_use, self.used_pages)
        return ids

    def share(self, ids) -> None:
        """Take one extra reference on each already-allocated page —
        the prefix-sharing path (a hit maps a donor's run; the prefix
        index pins published runs).  Validates the whole list before
        mutating: sharing a free or out-of-range page raises
        :class:`PageAllocatorError` with the allocator untouched."""
        arr = [int(i) for i in ids]
        for i in arr:
            if not 0 < i < self.num_pages:
                raise PageAllocatorError(f"sharing invalid page id {i}")
            if self._refs[i] <= 0:
                raise PageAllocatorError(
                    f"sharing unallocated page {i} (refcount 0)")
        for i in arr:
            self._refs[i] += 1

    def release(self, ids) -> None:
        """Drop one reference per listed page; a page returns to the free
        list only when its refcount reaches 0.  The WHOLE list is
        validated before any mutation: an out-of-range id or an
        over-release (double free, or a page listed more often than it
        has references) raises :class:`PageAllocatorError` and leaves
        every refcount and the free list exactly as they were."""
        counts = Counter(int(i) for i in ids)
        for i, c in counts.items():
            if not 0 < i < self.num_pages:
                raise PageAllocatorError(f"releasing invalid page id {i}")
            if self._refs[i] < c:
                raise PageAllocatorError(
                    f"over-release of page {i}: {c} release(s) against "
                    f"refcount {int(self._refs[i])} — double free")
        for i, c in counts.items():
            self._refs[i] -= c
            if self._refs[i] == 0:
                self._free.append(i)

    # single-owner aliases (pre-refcount API; scheduler internals, fault
    # injection, and older tests call these)
    def alloc(self, n: int) -> Optional[np.ndarray]:
        return self.acquire(n)

    def free(self, ids) -> None:
        self.release(ids)

    def hold(self, n: int) -> np.ndarray:
        """Take up to ``n`` pages out of circulation — injected allocator
        exhaustion (``serving.faults.HoldPages``) or reserved headroom.
        Grants whatever headroom exists (possibly zero ids) instead of
        refusing like :meth:`acquire`; return the ids with
        :meth:`release`."""
        n = min(n, len(self._free))
        if n <= 0:
            return np.zeros((0,), np.int32)
        ids = self.acquire(n)
        return ids if ids is not None else np.zeros((0,), np.int32)

    def utilization(self) -> float:
        return self.used_pages / max(1, self.num_pages - 1)

    def check_consistency(self) -> None:
        """Audit the free-list/refcount partition; raises
        :class:`PageAllocatorError` on the first violated invariant.
        The invariants: the null page is never referenced, refcounts are
        never negative, the free list holds no duplicates, free pages
        have refcount 0, and every non-null page is either free or
        referenced (no page is ever lost or granted twice)."""
        if self._refs[NULL_PAGE] != 0:
            raise PageAllocatorError("null page has a nonzero refcount")
        if (self._refs < 0).any():
            bad = int(np.argmin(self._refs))
            raise PageAllocatorError(
                f"negative refcount on page {bad}: {int(self._refs[bad])}")
        if len(set(self._free)) != len(self._free):
            raise PageAllocatorError("duplicate ids on the free list")
        for i in self._free:
            if not 0 < i < self.num_pages:
                raise PageAllocatorError(f"invalid id {i} on the free list")
            if self._refs[i] != 0:
                raise PageAllocatorError(
                    f"page {i} is on the free list with refcount "
                    f"{int(self._refs[i])}")
        allocated = int((self._refs[1:] > 0).sum())
        if len(self._free) + allocated != self.num_pages - 1:
            raise PageAllocatorError(
                f"page accounting broken: {len(self._free)} free + "
                f"{allocated} allocated != {self.num_pages - 1} pages")


def init_paged_pool(cfg, *, num_pages: int, page_size: int,
                    dtype=jnp.float32):
    """Zeroed page-pool cache pytree ``{"prefix": [], "stack": (K, V)}``.

    Layer axis leads, mirroring the contiguous stack layout.  The dense
    paged decode step takes the pool donated, carries it whole through
    its layer loop and writes each slot's current page at ``[layer,
    page]``, so the pool is updated in place and never held twice; the
    sparse twins scan one layer's ``(num_pages, Hkv, page_size,
    head_dim)`` slice.  MLA configs get the one latent pool
    ``{"prefix": [], "stack": (latent,)}`` over all ``num_layers``.
    """
    from repro.models.transformer import num_prefix_layers
    if cfg.mla.enabled:
        shape = (cfg.num_layers, num_pages, 1, page_size,
                 cfg.mla.latent_dim)
        return {"prefix": [], "stack": (jnp.zeros(shape, dtype),)}
    if num_prefix_layers(cfg):
        raise ValueError("paged KV cache covers the scanned stack only")
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, num_pages, cfg.num_kv_heads, page_size, hd)
    # under a sharding-rules context the pool is born split along Hkv, the
    # axis the sharded decode kernel partitions; unmeshed this is a no-op
    k, v = (shard(jnp.zeros(shape, dtype), None, None, "kv_heads")
            for _ in range(2))
    return {"prefix": [], "stack": (k, v)}


def _scatter_whole(pool, val, pages):
    """val (L, Hkv, S, hd) → pool pages along every layer."""
    l, hkv, s, hd = val.shape
    ps = pool.shape[3]
    npg = s // ps
    v = val.reshape(l, hkv, npg, ps, hd).transpose(0, 2, 1, 3, 4)
    return pool.at[:, pages].set(v.astype(pool.dtype))


@functools.partial(jax.jit, donate_argnums=0)
def _write_latent(pool, layers, rows, pages):
    """pool[layers, pages] = rows ``(len(layers), S, W)`` page by page,
    the pool donated."""
    n, s, w = rows.shape
    ps = pool.shape[3]
    tiles = rows.reshape(n, s // ps, ps, w).astype(pool.dtype)
    return pool.at[layers[:, None], pages[None, :], 0].set(tiles)


def _insert_latent(cache, layers, rows, pages):
    (pool,) = cache["stack"]
    pool = _write_latent(pool, jnp.asarray(list(layers), jnp.int32), rows,
                         jnp.asarray(pages, jnp.int32))
    return {"prefix": [], "stack": (pool,)}


def insert_latent_layer(cache, layer: int, rows, pages):
    """Write one layer's prefill latent rows ``(1, S, latent_dim)`` into
    pages of the latent pool (chunked admission, layer by layer)."""
    return _insert_latent(cache, [layer], rows, pages)


def insert_prefill(cache, new, pages):
    """Write a freshly prefilled request's stacked KV (leaves
    ``(L, 1, Hkv, S, hd)``) into its ``S // page_size`` pages.  Under
    MLA, ``new`` holds ``(c_kv, k_rope)`` per prefix layer and stacked,
    and the pool takes their rows over every layer."""
    if len(cache["stack"]) == 1:
        rows = jnp.concatenate(
            [jnp.concatenate(c, axis=-1) for c in new["prefix"]]
            + [jnp.concatenate(new["stack"], axis=-1)[:, 0]])
        return _insert_latent(cache, range(len(rows)), rows, pages)
    if new["prefix"]:
        raise ValueError("paged KV cache covers the scanned stack only")
    ck, cv = cache["stack"]
    nk, nv = new["stack"]
    pages = jnp.asarray(pages, jnp.int32)
    return {"prefix": [], "stack": (_scatter_whole(ck, nk[:, 0], pages),
                                    _scatter_whole(cv, nv[:, 0], pages))}


def insert_prefill_layer(cache, layer: int, k, v, pages, *, offset: int = 0,
                         length: Optional[int] = None):
    """Write one layer's prefill K/V ``(1, Hkv, S, hd)`` into pages.

    Chunked-prefill counterpart of :func:`insert_prefill`: KV lands
    layer-by-layer as each scan step finalizes; packed multi-prompt
    segments are sliced out with ``offset``/``length`` first.
    """
    if length is not None:
        k = slice_segment(k, offset, length, axis=2)
        v = slice_segment(v, offset, length, axis=2)
    ck, cv = cache["stack"]
    ps = ck.shape[3]
    pages = jnp.asarray(pages, jnp.int32)

    def ins(pool, val):
        _, hkv, s, hd = val.shape
        npg = s // ps
        vv = val[0].reshape(hkv, npg, ps, hd).transpose(1, 0, 2, 3)
        return pool.at[layer, pages].set(vv.astype(pool.dtype))

    return {"prefix": [], "stack": (ins(ck, k), ins(cv, v))}


def copy_page(cache, src: int, dst: int):
    """Copy one page's K/V (every layer) from page ``src`` to ``dst`` —
    the copy half of copy-on-write at the decode boundary.

    A slot about to append into a *shared* page (refcount > 1: a prefix
    cache hit mapped it, or the prefix index pinned it) acquires a fresh
    page, copies the shared page's partial block here, and rewrites its
    table entry; the original stays read-only for the other holders.
    The ``.at[].set`` runs outside jit and copies the pool once per COW —
    bounded by the decode-tail page count per request, and the pool is
    small on the CPU smoke configs this repo serves (donated-buffer jit
    would avoid the copy on accelerators if it ever matters)."""
    return {"prefix": [], "stack": tuple(p.at[:, dst].set(p[:, src])
                                         for p in cache["stack"])}


def page_bytes(cfg, page_size: int, itemsize: int = 4) -> int:
    """Bytes one page holds across all layers, K and V (the latent row
    under MLA)."""
    if cfg.mla.enabled:
        return cfg.num_layers * page_size * cfg.mla.latent_dim * itemsize
    return (2 * cfg.num_layers * cfg.num_kv_heads * page_size
            * cfg.resolved_head_dim * itemsize)


def contiguous_kv_bytes(cfg, batch: int, cache_len: int,
                        itemsize: int = 4) -> int:
    """Bytes the contiguous scheduler holds for the same decode batch."""
    return (2 * cfg.num_layers * batch * cfg.num_kv_heads * cache_len
            * cfg.resolved_head_dim * itemsize)
