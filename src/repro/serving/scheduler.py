"""Slot-based continuous-batching scheduler for the serving engine.

Owns the request lifecycle

    WAITING → PREFILLING → DECODE → {DONE, FAILED, CANCELLED}
                  ▲                      │
                  └──── PREEMPTED ◄──────┘   (paged pool starvation:
                        (back to WAITING,     pages reclaimed, generated
                         tokens carried,      tokens replayed through
                         replay on resume)    decode after re-prefill)

over a persistent fixed-shape decode state of ``max_batch`` *slots*.
Terminal states map to ``Request.finish_reason``: DONE ← "stop"/"length",
CANCELLED ← "cancelled" (a :class:`SchedulerHandle.cancel`) or "timeout"
(``Request.deadline_s`` exceeded), FAILED ← "failed" (runtime quarantine)
or "rejected" (submit-time validation, before the scheduler ever sees the
request).  Core slot mechanics:

  * **Per-slot positions.**  Every slot decodes at its own ``pos`` (the
    ``(B,)`` vector contract of ``transformer.decode_step`` /
    ``attention_decode``): a fresh request starts at the prefill boundary
    while its neighbours are deep into their decode tails, and the
    slot-validity mask is per-row, so rows never see each other's state.
  * **In-flight slot replacement.**  When a slot finishes (stop token or
    its own ``max_new_tokens``) it is freed immediately and the next
    WAITING request is admitted: its KV is written into the slot's cache
    row (:meth:`ServingEngine.cache_insert` /
    :meth:`~ServingEngine.cache_insert_layer`) and — under
    ``decode_sparse`` — its freshly built DecodePlan row spliced into the
    live plan (``decode_plan.update_plan_slot_auto``; Hkv-sharded under a
    mesh) without touching the other slots' tables.  An admission whose
    prefill yields no pattern dictionary (``sp_state is None``) gets the
    all-keep ``decode_plan.dense_decode_plan`` row — a *per-request* dense
    fallback; the other slots (and later admissions) stay sparse.
  * **Step-cadence chunked admission** (``EngineConfig.prefill_chunk``).
    With one-shot admission every occupied slot stalls for the entire
    prefill launch — the decode-throughput cliff this scheduler originally
    shipped with.  In chunked mode an admission becomes a
    :class:`~repro.serving.chunked_prefill.ChunkedPrefillRun` — a sequence
    of small quanta (mask staging / rectangular Q-chunk attention / FFN +
    dictionary update, per layer) — and the main loop interleaves **at
    most one quantum with each decode step**, so the stall per step is
    bounded by the largest single quantum instead of the whole prefill.
    Each layer's K/V is inserted into the admitted slot as soon as its
    quantum completes (safe: prefill writes land in ``[0, seq)`` while
    inert-slot decode writes stay at the frozen tail position); the
    DecodePlan row and first sampled token happen only when the final
    quantum completes, so a half-prefilled slot is never decoded.
  * **Multi-prompt prefill packing** (``EngineConfig.prefill_pack``).
    Several short queued prompts concatenate into ONE chunked run — per-
    segment positions, a block-diagonal isolation mask, one kernel launch
    — and each segment's K/V slice lands in its own slot, with per-segment
    DecodePlan rows cut from the packed pattern dictionary
    (``sparse_decode.packed_decode_keep_blocks``).  Packing needs the masked
    prefill path (``method != "dense"``, pattern sharing applicable, no
    sliding window); unpackable configs admit one prompt per run.
  * **Block-paged decode state** (``EngineConfig.paged``).  Slots stop
    owning contiguous cache rows: decode KV lives in one shared page pool
    ``(L, num_pages, Hkv, page_size, hd)`` (``repro.serving.paged_cache``,
    ``page_size == block_size``, page 0 reserved null) addressed through a
    per-slot ``(table_blocks,)`` page-table row.  Admission allocates
    ``(bucket + extra) / page_size`` pages from a host-side free list —
    and is *gated on pool headroom*: a request whose pages are not
    available stays WAITING (``engine.pages_exhausted_steps`` counts the
    deferrals) until a finishing slot frees its pages (``__init__``
    validates the pool holds at least one max-length request, so decode
    progress guarantees eventual admission).  Prefill KV is scattered
    page-at-a-time (whole-cache on the one-shot path, per layer under
    chunked admission) and the decode step rewrites each slot's current
    page in the donated pool — no ``grow_cache`` reallocation, no
    whole-row ``cache_insert`` copies.  Because batch geometry is now just
    page-table rows, ONE paged scheduler serves ALL buckets: each request
    prefills at its own bucket, keeps a per-slot ``prefill_len``
    (``pflens``), and its DecodePlan row — built at its own allocation
    ``bucket + extra`` — is padded to the shared table width
    (``decode_plan.pad_plan_row``) so mixed-length slots coexist in one
    fixed-shape decode batch.  The DecodePlan block tables and the page
    tables are thereby *unified*: a head's keep-set IS its set of resident
    pages, and the page-aware kernel twins translate only the K/V DMA
    address, staying bitwise-equal to the contiguous kernels.
  * **Inert slots.**  An unoccupied slot keeps decoding (fixed-shape jitted
    step) but its tables are empty / its sampled tokens discarded; validity
    masking means stale cache values never reach a softmax, so occupied
    rows are bitwise independent of slot churn — with greedy sampling the
    scheduler's output tokens bit-match the legacy batch-at-a-time serve,
    and chunked admission keeps the same guarantee (per-request sampling
    keys derive from ``uid``; rows are independent, so admission cadence
    cannot change any request's token stream).
    (Caveat: under the adaptive width policies — ``width_policy="auto"`` /
    ``"count"`` — the prefill cap freezes after the first *observation*,
    which is per single-request prefill here but per batch in the legacy
    path, so later requests may prefill under different caps across the
    two paths; the bit-match guarantee holds for ``width_policy="off"`` or
    once both paths' caps are frozen equal.)

The scheduler reuses the engine's compiled-program caches (prefill at
batch 1 or the chunk-quantum cache; the decode program retraces once for
vector ``pos``), its width policies, and its slot-occupancy accounting.
Admission interference is *measured*, not inferred: every prefill quantum
(or one-shot launch), K/V insert and run completion adds to
``engine.phase_s["prefill"]`` — decode steps and idle sleeps likewise —
and wall time a request's admission spent while ≥ 1 slot was occupied
lands in that request's ``prefill_stall_s`` (split across a packed run's
segments).  Each of those is a named span of ``repro.serving.tracing``
(one ``sched_step`` per scheduler step), so a profiler trace puts the
device's program runs and idle gaps down to the host work behind them.

Arrival simulation: requests carry ``arrival_s`` offsets (relative to
``serve()`` start); a request is admitted only once its arrival time has
passed — the scheduler sleeps only when every slot is idle.  Per-request
metrics are real, not batch-wide copies: ``queue_s`` (arrival → prefill
start), ``ttft_s`` (arrival → first token), ``decode_s`` /
``decode_tokens_per_s`` (first token → last token).

**Lifecycle hardening.**  Every scheduler step begins with a reap pass
(:meth:`SlotScheduler._reap`): requests cancelled through the serve's
:class:`SchedulerHandle` (or an injected :class:`~repro.serving.faults.
CancelAt`) and requests whose ``deadline_s`` wall budget has expired are
terminated wherever they stand — WAITING requests finish inert,
DECODE slots are vacated (pages freed, plan row emptied before the next
decode step), and an in-flight chunked admission aborts cleanly *between*
quanta (:meth:`ChunkedPrefillRun.abort`; a packed run aborts only once
every segment is doomed — live segments ride the run to completion).

**Preemption with page reclaim** (``EngineConfig.preempt_after_steps``,
paged mode): when the queue head has been deferred on pool headroom for
more than the configured number of consecutive steps, the lowest-priority
decoding victim (``Request.priority``, ties → fewest generated tokens) is
evicted — slot vacated, pages returned to the free list, plan row emptied
— and re-enqueued WAITING with its generated tokens carried in
``resume_tokens``.  A later admission re-prefills the ORIGINAL prompt at
its original bucket — bitwise the first admission — and replays the carry
through ordinary decode steps as forced tokens: decode rows share nothing
across the batch axis and the sampling-key chain restarts from the same
``fold_in`` and splits in the same order, so the resumed stream (and its
continuation) reproduces the unpreempted serve bitwise, greedy or
sampled.  Head-of-line starvation becomes bounded-latency degradation,
and a resume's page footprint never exceeds its first admission's.  A
forward-progress guard makes the churn livelock-free: a slot is only
evictable once its carried stream is strictly longer than the carry it
was admitted with, so every eviction cycle nets at least one new token.

**Per-request fault quarantine.**  A cheap per-row ``np.isfinite`` guard
on the host-pulled decode logits vacates ONLY the poisoned slot
(``finish_reason="failed"``, the typed
:class:`~repro.serving.errors.RequestError` in ``Request.error``); the
other slots' rows share nothing across the batch axis, so their tokens are
bitwise-unaffected.  Admission prefill — the one-shot launch and every
chunked quantum — runs under try/except isolation: an exception fails only
the admitting request(s) (a packed run's segments share the kernel launch,
so the quarantine granularity there is the run), releases their pages, and
the serve continues.  The :class:`~repro.serving.faults.FaultInjector`
passed via ``serve(faults=...)`` drives all of these paths
deterministically; the end-of-serve pool summary records
``pages_in_use_at_end`` so leak-freedom is observable.

**Prefix sharing + copy-on-write** (``EngineConfig.prefix_sharing``,
paged mode): when a cold prefill completes, the request's full page run
(prompt pages + decode tail) is published to a
:class:`~repro.serving.prefix_cache.PrefixIndex` keyed on the digest of
the **clipped** prompt at its bucket (plus a model salt) — the index
holds one extra refcount per page, so the run is read-only from that
moment on.  A queued request whose digest (and current width-policy cap)
matches skips the prefill launch entirely: admission maps the published
pages into its page table (``PageAllocator.share`` — refcount++, zero
pages acquired, headroom gate skipped), replays the donor's cached
first-token logits, DecodePlan row, and width-policy observation, and
proceeds straight to decode.  Because a full-prompt hit replays the same
deterministic compiled program's outputs on identical inputs, the hit's
token stream is bitwise the cold serve — greedy or sampled (sampling
keys derive from the hit's own ``uid``).  Writes are fenced at the
decode boundary: before each decode step, any slot whose append-target
page has refcount > 1 (the donor's own tail included) is moved onto a
fresh private page first — ``paged_cache.copy_page`` + page-table/
``slot_pages`` rewrite + release of the shared page
(:meth:`SlotScheduler._cow_append_page`).  The index is a cache, so it
yields under memory pressure: both a starved cold admission
(:meth:`SlotScheduler._shed_index_for`) and a COW copy that cannot
acquire a page evict LRU entries for headroom, and COW as a last
resort preempts the writing slot itself through the ordinary bitwise
preempt/resume machinery.  Packed runs (``prefill_pack`` > 1 segments) are never
published — the pack-fusion delta is greedy-exact but not bitwise — and
the index is cleared (all references released) before the end-of-serve
pool summary, so the zero-leak invariant is unchanged.

**Adaptive pattern refresh** (``EngineConfig.refresh_every``, paged +
``decode_sparse``): a frozen DecodePlan row keeps the sparse prefill
pattern but accretes a *dense* recent tail — every appended block is
force-kept, so a long decode's traffic fraction climbs back toward 1.
With refresh on, each occupied slot records its last ``block_size``
decode queries into a host-side ring (:class:`~repro.serving.refresh.
RefreshState`; the decode step runs a ``collect_queries`` twin that also
returns the per-slot query vectors), and every ``refresh_every`` steps —
or earlier when the slot's tail fraction crosses
``refresh_tail_threshold`` — the scheduler re-estimates the row from the
live paged KV: ``decode_plan.build_refresh_plan_row`` scores the slot's
resident pages against the query window (the strip kernel's paged twin),
converts per-head attention mass into ragged budgets
(``width_policy.score_mass_budgets`` → ``indices.ragged_top_mask``), and
force-keeps only a bounded dense *horizon* of upcoming append blocks in
place of the unbounded tail.  The refreshed row is spliced like any
admission row, with the plan width re-bucketed to the global max need
(``set_plan_width`` / ``bucket_plan_width``, power-of-two widths so
recompiles stay O(log NB)).  Lifecycle rules: refresh state is created at
admission (cold or prefix-hit), dropped on vacate AND on preemption (a
resume re-warms a cold window); a slot any of whose pages are still
COW-shared (refcount > 1 — donor or hit) defers its refresh untouched
(``refresh_stats["deferred_cow"]``) and relies on
``extend_plan_row_horizon`` if an append would outrun its horizon; a
mid-prefill chunked admission is structurally unreachable (only occupied
slots tick).  Refresh trades the frozen-plan bitwise guarantee for
measured traffic reduction; ``refresh_every=0`` (default) never records
queries, runs the original decode program, and stays bitwise-identical.

MLA latent caches and the non-transformer families never reach this module
— ``ServingEngine.serve`` routes them through the legacy batch path (the
dense carve-out; their caches have no per-slot write layout).  Configs a
chunked admission cannot serve (``ServingEngine._chunk_tokens`` → 0) keep
the one-shot admission path unchanged.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
import time
import types
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import decode_plan as dplan
from repro.serving import paged_cache
from repro.serving import prefix_cache
from repro.serving import refresh as refresh_mod
from repro.serving import sparse_decode
from repro.serving import tracing
from repro.serving.chunked_prefill import ChunkedPrefillRun
from repro.serving.errors import RequestError
from repro.serving.sampling import sample_token

logger = logging.getLogger(__name__)


class SchedulerHandle:
    """Thread-safe cancellation surface for an in-flight ``serve()``.

    Create one, pass it to :meth:`ServingEngine.serve(handle=...)`, and
    call :meth:`cancel` (from any thread) to terminate a request at the
    scheduler's next step: WAITING requests finish immediately with
    ``finish_reason="cancelled"``, DECODE slots are vacated (pages freed,
    empty plan row spliced), and an in-flight chunked admission aborts
    between quanta.  Cancelling an unknown or already-finished uid is a
    no-op."""

    def __init__(self):
        self._lock = threading.Lock()
        self._uids: set = set()

    def cancel(self, uid: int) -> None:
        with self._lock:
            self._uids.add(uid)

    def cancelled(self) -> frozenset:
        with self._lock:
            return frozenset(self._uids)


@dataclasses.dataclass
class _Slot:
    """One occupied decode slot (engine ``Request`` + its live decode
    state: sampling key stream, emitted tokens, last token to feed)."""
    req: "Request"                      # noqa: F821 (engine import cycle)
    key: jax.Array
    outs: List[int]
    last_tok: int
    t_first: float                      # wall time of the first token
    replay: List[int] = dataclasses.field(default_factory=list)
                                        # preemption carry not yet re-fed:
                                        # decode steps force these tokens
                                        # (instead of the sampled one)
                                        # until the list drains
    carry_len: int = 0                  # carry length at admission — a slot
                                        # is evictable only once its stream
                                        # has grown past this (progress
                                        # guard, see _preempt_victim)


class SlotScheduler:
    """Continuous-batching serve of one sequence bucket's requests."""

    def __init__(self, engine, requests, seq: int, *, seed: int = 0,
                 t0: Optional[float] = None, paged: bool = False):
        self.eng = engine
        self.seq = seq
        self.seed = seed
        self.paged = bool(paged and engine.ecfg.paged)
        self.t0 = time.time() if t0 is None else t0
        # FIFO in arrival order (stable: same-arrival requests keep their
        # submission order, matching the legacy path's batch grouping)
        self.queue = deque(sorted(requests, key=lambda r: r.arrival_s))

        ecfg = engine.ecfg
        self.nslots = ecfg.max_batch
        blk = max(engine.sp.cfg.block_size, 1)

        # lifecycle hardening: the serve's cancellation handle and fault
        # injector (both may be None), the 1-based step counter the reaper
        # and injector key on, the consecutive-starvation counter behind
        # preemption, and the doom list for in-flight run segments
        self.handle = getattr(engine, "handle", None)
        self.faults = getattr(engine, "faults", None)
        self.step_i = 0
        self._starved = 0
        self._doomed: dict = {}         # uid → terminal reason, applied at
                                        # run abort/completion
        self.preempt_after = (ecfg.preempt_after_steps
                              if self.paged and ecfg.preempt_after_steps > 0
                              else 0)

        # one cache headroom for the whole bucket: covers the longest
        # request and stays a block multiple so the DecodePlan tables tile
        # the grown region exactly (same rounding as the legacy path)
        extra = max(max(r.max_new_tokens for r in requests),
                    ecfg.decode_extra)
        self.cache_len = seq + ((extra + blk - 1) // blk) * blk

        # persistent fixed-shape decode state; the cache is created on the
        # first admission so it inherits the prefill cache's dtype (the
        # legacy path gets this via grow_cache — init_cache's f32 default
        # would break non-f32 models at the first per-slot write)
        self.slots: List[Optional[_Slot]] = [None] * self.nslots
        self.pos = np.full((self.nslots,), seq, np.int32)
        self.plens = np.full((self.nslots,), seq, np.int32)
        # per-slot prefill length: constant ``seq`` in contiguous mode (one
        # bucket per scheduler), genuinely ragged once buckets mix under
        # paging (decode_step accepts the (B,) vector form)
        self.pflens = np.full((self.nslots,), seq, np.int32)
        self.cache = None
        # MLA pages its latent rows (one pool over every layer)
        self.latent = self.paged and engine.model.cfg.mla.enabled

        # block-paged pool state: host-side free-list allocator + the
        # per-slot page table the paged kernels scalar-prefetch.  Every
        # slot's table is sized at the *virtual* width (largest bucket +
        # decode tail); unheld entries stay NULL_PAGE and are never
        # streamed (plan rows are padded keep-False past the allocation).
        self.page_size = blk
        self.extra_len = self.cache_len - seq   # block-rounded decode tail
        if self.paged:
            if seq % blk:
                raise ValueError(
                    f"paged serving needs block-aligned seq buckets; got "
                    f"bucket {seq} with page_size {blk}")
            self.table_blocks = self.cache_len // blk
            # auto-sizing: every slot can hold a full run — plus, under
            # prefix sharing, headroom for what sharing adds on top of
            # slot-held runs (one published run pinned by the index and
            # one COW tail per slot); without it the exactly-sized pool
            # COW-exhausts on every shared decode and churns through
            # preempt/resume cycles instead of just copying a page
            share_extra = ((self.table_blocks + self.nslots)
                           if ecfg.prefix_sharing else 0)
            cap = ecfg.num_pages or (1 + self.nslots * self.table_blocks
                                     + share_extra)
            if cap - 1 < self.table_blocks:
                raise ValueError(
                    f"num_pages={cap} cannot hold one max-length request "
                    f"({self.table_blocks} pages + the null page): "
                    "admission would deadlock")
            self.num_pages = cap
            self.alloc = paged_cache.PageAllocator(cap)
            self.page_table = np.full((self.nslots, self.table_blocks),
                                      paged_cache.NULL_PAGE, np.int32)
            self.slot_pages: dict = {}
        # prompt-prefix sharing (repro.serving.prefix_cache): completed
        # prefills publish their page run under a digest of the CLIPPED
        # prompt; an identical later prompt maps the pages read-only and
        # skips its prefill launch.  Shared pages are protected by the
        # COW guard at the decode boundary (_cow_append_page).
        self.prefix = None
        self._cow_copies = 0
        if self.paged and ecfg.prefix_sharing:
            self.prefix = prefix_cache.PrefixIndex(ecfg.prefix_max_entries)
            mcfg = engine.model.cfg
            self._prefix_salt = (
                f"{getattr(mcfg, 'name', '')}/{mcfg.family}/"
                f"{mcfg.num_layers}/{mcfg.num_heads}/"
                f"{mcfg.resolved_head_dim}")
        # decode-phase pattern sharing: committed up front from the config
        # AND the bucket's pattern applicability — the predicate that makes
        # the per-request `sp_state is None` fallback (dense_decode_plan in
        # _start/_complete_run) genuinely per-request instead of the old
        # sticky scheduler-wide disable
        # (paged mode drops the bucket-wide applicability term: prefill
        # runs per request bucket, and a bucket whose prefill yields no
        # pattern dictionary gets the per-request dense row below)
        self.use_sparse = (ecfg.decode_sparse and ecfg.method == "share"
                           and engine._supports_sparse_decode()
                           and engine.sp.cfg.enabled
                           and (self.paged or engine.sp.applicable(seq)))
        self.plan = None
        self._empty_row = None
        self._stale_slots = set()       # vacated, plan row not yet emptied
        if self.use_sparse:
            self.plan = dplan.empty_decode_plan(
                engine.model.cfg, batch=self.nslots,
                cache_len=self.cache_len, block_size=blk)
            # spliced back over a vacated slot's tables so inert slots
            # stream nothing (the empty-keep contract; a dead request's
            # keep-set must not keep burning memory bandwidth)
            self._empty_row = dplan.empty_decode_plan(
                engine.model.cfg, batch=1, cache_len=self.cache_len,
                block_size=blk)

        # adaptive pattern refresh (EngineConfig.refresh_every, paged +
        # sparse only): per-slot recent-query rings, the host-side copy of
        # each slot's last spliced plan row (tail accounting + cheap
        # horizon extensions), and the per-slot max kept count behind the
        # live plan's narrowed table width.  refresh_on=False keeps every
        # splice on the exact pre-refresh path (full-width plans, same
        # compiled programs) — the default-off serve is bitwise-unchanged.
        self.refresh_on = bool(self.paged and self.use_sparse
                               and ecfg.refresh_every > 0)
        self.refresh: dict = {}         # slot → refresh_mod.RefreshState
        self._slot_rows: dict = {}      # slot → last spliced full-width row
        self._row_need: dict = {}       # slot → host max kept count (width
                                        # bucketing input)
        self.horizon_blocks = 0
        if self.refresh_on:
            self.horizon_blocks = (ecfg.refresh_horizon_blocks
                                   or ecfg.refresh_every // blk + 1)

        # step-cadence chunked admission (0 = one-shot path)
        self.chunk = engine._chunk_tokens(seq)
        self.run_: Optional[ChunkedPrefillRun] = None
        self._run_wall = 0.0

    # -- lifecycle ------------------------------------------------------
    def run(self) -> None:
        try:
            if self.chunk:
                self._run_chunked()
            else:
                while self.queue or any(s is not None for s in self.slots):
                    with self._sched_step():
                        self._step_begin()
                        self._admit()
                        self._flush_stale_slots()
                        if any(s is not None for s in self.slots):
                            self._decode_step()
                self._flush_stale_slots()   # leave the documented
                                            # invariant: unoccupied slots'
                                            # tables are empty
        finally:
            # injected page-exhaustion windows must never leak pool pages,
            # the prefix index must drop its pinned page references, and
            # the pool summary (with its end-of-serve leak accounting)
            # must publish even if the serve itself blew up
            if self.prefix is not None:
                self.prefix.clear(self.alloc)
            if self.faults is not None and self.paged:
                self.faults.release_pages(self.alloc)
            self._pool_summary()

    def _run_chunked(self) -> None:
        """Chunked main loop: one prefill quantum, then one decode step —
        the fair-share cadence that bounds admission stall per step."""
        while (self.queue or self.run_ is not None
               or any(s is not None for s in self.slots)):
            with self._sched_step():
                self._step_begin()
                self._prefill_step()
                if (self.run_ is not None and self.paged and self.queue
                        and (self.t0 + self.queue[0].arrival_s)
                        <= time.time()
                        and self._prefix_entry(self.queue[0]) is None):
                    self._shed_index_for(self.queue[0])
                    if (self.alloc.free_pages
                            < self._pages_needed(self.queue[0])):
                        # the queue head would be starved even once the
                        # in-flight run lands — keep the starvation clock
                        # ticking so a decoding victim can be evicted
                        # mid-chunked-admission
                        self._note_starved(self.queue[0])
                self._flush_stale_slots()
                if any(s is not None for s in self.slots):
                    self._decode_step()
        self._flush_stale_slots()

    def _sched_step(self) -> tracing.step:
        """Advance the step counter; the step runs inside the returned
        ``sched_step`` span (queue length and free pages at its start)."""
        self.step_i += 1
        return tracing.step(self.eng, self.step_i, waiting=len(self.queue),
                            free_pages=self.alloc.free_pages
                            if self.paged else 0)

    def _step_begin(self) -> None:
        """Per-step lifecycle tick: let the fault injector act (due
        cancels, page-exhaustion windows), then reap cancelled /
        deadline-expired requests."""
        if self.faults is not None:
            self.faults.on_step(self.step_i,
                                alloc=self.alloc if self.paged else None)
        with tracing.span("reap"):
            self._reap()

    def _reap(self) -> None:
        """Terminate cancelled / deadline-expired requests wherever they
        stand in the lifecycle: WAITING (finish inert), mid-chunked-prefill
        (doom the segment; abort the run between quanta once no live
        segment remains), or DECODE (vacate — pages freed, plan row
        emptied before the next decode step)."""
        cancelled = set()
        if self.handle is not None:
            cancelled |= self.handle.cancelled()
        if self.faults is not None:
            cancelled |= self.faults.cancelled()
        now = time.time()

        def doom_reason(r):
            if r.uid in cancelled:
                return "cancelled"
            if (r.deadline_s > 0
                    and now - (self.t0 + r.arrival_s) > r.deadline_s):
                return "timeout"
            return None

        for r in list(self.queue):
            reason = doom_reason(r)
            if reason is not None:
                self.queue.remove(r)
                self._finish_inert(r, reason)
        run = self.run_
        if run is not None:
            for r in run.requests:
                if r.uid in self._doomed:
                    continue
                reason = doom_reason(r)
                if reason is not None:
                    self._doomed[r.uid] = reason
            if all(r.uid in self._doomed for r in run.requests):
                self._abort_run(run)
        for i, s in enumerate(self.slots):
            if s is not None and doom_reason(s.req) is not None:
                self._vacate(i, s, doom_reason(s.req))

    def _finish_inert(self, r, reason: str, error=None) -> None:
        """Finalize a request that holds no decode slot (WAITING, or a
        doomed/quarantined admission): terminal metrics without slot
        bookkeeping.  A preempted request's carried tokens are its output
        so far."""
        if error is not None and r.error is None:
            r.error = error
        self._finish(_Slot(req=r, key=jax.random.PRNGKey(0),
                           outs=list(r.resume_tokens), last_tok=0,
                           t_first=time.time()), reason)

    def _abort_run(self, run: ChunkedPrefillRun) -> None:
        """Abort an in-flight chunked admission between quanta: release
        the granted pages, finalize every doomed segment, drop the run's
        device state.  Callers doom every live segment first — a packed
        run's segments share the kernel launch, so the abort granularity
        is the whole run."""
        if self.paged:
            for slot in run.slot_ids:
                self._release_pages(slot)
        for r in run.requests:
            reason = self._doomed.pop(r.uid, "cancelled")
            if not r.finish_reason:
                self._finish_inert(r, reason)
        run.abort()
        self.run_ = None

    def _quarantine_run(self, run: ChunkedPrefillRun, exc: Exception
                        ) -> None:
        """A prefill quantum raised: every live segment of the run is
        FAILED (per-request quarantine at run granularity — packed
        segments share the launch), pages released, device state dropped.
        The rest of the serve continues untouched."""
        for r in run.requests:
            if r.finish_reason or r.uid in self._doomed:
                continue
            if isinstance(exc, RequestError) and exc.uid == r.uid:
                err = exc
            elif isinstance(exc, RequestError):
                err = RequestError(
                    r.uid, f"packed run failed alongside request "
                    f"{exc.uid}", kind="prefill")
            else:
                err = RequestError(
                    r.uid, f"prefill quantum raised "
                    f"{type(exc).__name__}: {exc}", kind="prefill")
            self._doomed[r.uid] = "failed"
            r.error = err
            logger.warning("quarantined: %s", err)
        self._abort_run(run)

    def _pool_summary(self) -> None:
        """Publish the pool's capacity/peak/leak accounting on the engine."""
        if not self.paged:
            return
        stats = {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "table_blocks": self.table_blocks,
            "peak_pages": self.alloc.peak_in_use,
            "peak_utilization": (self.alloc.peak_in_use
                                 / max(1, self.num_pages - 1)),
            # every terminal transition frees its pages, so a drained serve
            # must report 0 here — the observable the leak gates pin
            "pages_in_use_at_end": self.alloc.used_pages,
        }
        if self.prefix is not None:
            pstats = self.prefix.stats()
            pstats["prefix_cow_copies"] = float(self._cow_copies)
            stats.update(pstats)
            self.eng.prefix_stats = pstats
        self.eng.page_pool_stats = stats

    def _flush_stale_slots(self) -> None:
        """Empty the plan rows of slots vacated since the last decode step.

        Deferred from :meth:`_vacate` so the common steady-state case —
        a finished slot immediately refilled by the next admission — pays
        one splice, not two; only a slot that actually stays inert for a
        decode step gets the empty row spliced in."""
        for slot in sorted(self._stale_slots):
            self._splice_row(slot, self._empty_row)
        self._stale_slots.clear()

    def _splice_row(self, slot: int, row) -> None:
        """Splice one slot's plan row into the live batch plan — the ONE
        path every row replacement takes (admission, prefix hit, chunked
        completion, stale-slot flush, refresh, horizon extension).

        With refresh off this is exactly the historical splice:
        ``update_plan_slot_auto`` on full-width rows, nothing else — the
        bitwise default path.  With refresh on it additionally manages the
        live plan's *narrowed table width*: the plan is widened (power-of-
        two buckets, :func:`decode_plan.bucket_plan_width`) when an
        incoming row keeps more blocks than the current W holds, the row
        is re-bucketed to the plan's W (lossless both ways —
        :func:`decode_plan.set_plan_width` guards narrowing), and once
        every live row fits a smaller bucket the whole plan narrows so the
        kernels' sequential grid — and the einsum fallback's gathered
        traffic — tracks the real refreshed budgets."""
        eng = self.eng
        if not self.refresh_on:
            self.plan = dplan.update_plan_slot_auto(self.plan, row, slot,
                                                    eng.model.cfg)
            return
        need = int(jnp.max(row.counts))
        self._row_need[slot] = need
        cur = self.plan.indices.shape[-1]
        if need > cur:
            self.plan = dplan.set_plan_width(
                self.plan, dplan.bucket_plan_width(need, self.table_blocks))
            cur = self.plan.indices.shape[-1]
        self.plan = dplan.update_plan_slot_auto(
            self.plan, dplan.set_plan_width(row, cur), slot, eng.model.cfg)
        target = dplan.bucket_plan_width(
            max(self._row_need.values(), default=1), self.table_blocks)
        if target < cur:
            self.plan = dplan.set_plan_width(self.plan, target)

    # -- paged-pool bookkeeping -----------------------------------------
    def _bucket_of(self, r) -> int:
        """A request's prefill geometry: the scheduler-wide bucket in
        contiguous mode (one bucket per scheduler instance), its own
        bucket under paging (mixed lengths coexist in one slot set)."""
        if not self.paged:
            return self.seq
        # a preempted request re-buckets at its ORIGINAL prompt length:
        # resume re-prefills the prompt alone (bitwise the first
        # admission) and replays the carry through decode steps, so its
        # geometry and page footprint never grow
        b = self.eng._bucket(len(r.prompt))
        if b % self.page_size:
            raise ValueError(
                f"seq bucket {b} is not a multiple of page_size "
                f"{self.page_size}; paged serving needs block-aligned "
                "buckets (page_size == pattern block_size)")
        return b

    def _pages_needed(self, r) -> int:
        """Pages one admission holds: its bucket plus the decode tail."""
        return (self._bucket_of(r) + self.extra_len) // self.page_size

    def _alloc_slot_pages(self, slot: int, n: int) -> np.ndarray:
        """Grant ``n`` pages to ``slot`` and map them in its table row.
        Callers gate on ``alloc.free_pages`` first — a failed grant here
        is a bookkeeping bug, not an admission-control event."""
        pages = self.alloc.alloc(n)
        if pages is None:               # pragma: no cover - guarded above
            raise RuntimeError("page allocation after headroom check")
        self.slot_pages[slot] = pages
        self.page_table[slot, :n] = pages
        return pages

    def _release_pages(self, slot: int) -> None:
        """Return a vacated slot's pages to the free list and null its
        table row.  Safe mid-flight: the slot is inert (its sampled tokens
        are discarded) and its plan row is flushed to the empty row before
        the next decode step, so recycled pages are never streamed through
        a stale table."""
        pages = self.slot_pages.pop(slot, None)
        if pages is not None:
            self.alloc.free(pages)
            self.page_table[slot, :] = paged_cache.NULL_PAGE

    def _shed_index_for(self, r) -> None:
        """Admission memory pressure: the prefix index is a cache, so its
        pinned page runs yield (LRU-first) before the queue head is
        deferred on headroom — or a decoding victim preempted.  Without
        this, a cold request can starve FOREVER against pages held only
        by the index: no slot is decoding, so starvation preemption has
        no victim and the run loop never makes progress.  Evicting an
        entry only frees pages no live slot still shares, so the loop is
        bounded by the index size."""
        if self.prefix is None:
            return
        while (len(self.prefix)
               and self.alloc.free_pages < self._pages_needed(r)):
            self.prefix.evict_one(self.alloc)

    def _note_starved(self, r) -> None:
        """The queue head's admission was deferred on pool headroom this
        step: count it per request (``waiting_deferred_steps``) and
        engine-wide, and — once the starvation window
        (``EngineConfig.preempt_after_steps``) is exceeded — evict a
        decoding victim so the head's pages eventually materialize."""
        self.eng.pages_exhausted_steps += 1
        r.waiting_deferred_steps += 1
        self._starved += 1
        if self.preempt_after and self._starved > self.preempt_after:
            self._preempt_victim()

    def _preempt_victim(self) -> None:
        """PREEMPTED → WAITING: evict the lowest-priority decoding slot
        (``Request.priority``, ties → fewest generated tokens), free its
        pages, and re-enqueue the request at the back of the queue with
        its generated tokens carried in ``resume_tokens``.  A later
        admission re-prefills the ORIGINAL prompt at its original bucket
        (bitwise the first admission) and replays the carry through
        ordinary decode steps as forced tokens — decode rows share
        nothing across the batch axis, so the resumed stream reproduces
        the unpreempted one bitwise (the sampling-key chain restarts from
        the same fold_in and splits in the same order).

        Forward-progress guard: a slot is only evicted once its carried
        stream (``outs + replay``) is STRICTLY longer than the carry it
        was admitted with.  Without it, starvation accumulated while an
        admission's chunked prefill is in flight (no victims exist yet,
        so the clock never resets) evicts the slot the moment its prefill
        lands — and a resumed slot would leave with exactly the carry it
        arrived with: zero net progress, livelock.  The guard *defers*
        the eviction rather than falling through to the next candidate,
        so it cannot promote a higher-priority slot into the victim."""
        cands = [i for i, s in enumerate(self.slots) if s is not None]
        if not cands:
            return
        victim = min(cands, key=lambda i: (self.slots[i].req.priority,
                                           len(self.slots[i].outs), i))
        s = self.slots[victim]
        if len(s.outs) + len(s.replay) <= s.carry_len:
            # chosen victim hasn't outgrown its admission carry yet; its
            # replay drains one token per decode step, so it becomes
            # evictable in bounded steps — hold the eviction until then
            return
        self._preempt_slot(victim, "pool starvation")

    def _preempt_slot(self, victim: int, why: str) -> None:
        """Evict one occupied slot PREEMPTED → WAITING: slot vacated,
        page references released, plan row staled, request re-enqueued
        with its generated tokens carried in ``resume_tokens``.  Shared
        mechanics of starvation preemption (:meth:`_preempt_victim`) and
        the COW-exhaustion fallback (:meth:`_cow_append_page`) — either
        way the resume replays the carry bitwise."""
        s = self.slots[victim]
        r = s.req
        npages = len(self.slot_pages.get(victim, ()))
        self.slots[victim] = None
        self._release_pages(victim)
        self._drop_refresh_slot(victim)
        if self.use_sparse:
            self._stale_slots.add(victim)
        # the full stream generated so far: earlier carry (if this is a
        # second eviction mid-replay) plus this occupancy's tokens
        r.resume_tokens = list(s.outs) + list(s.replay)
        r.preempted_count += 1
        r.state = "waiting"
        self.eng.preemptions += 1
        self.queue.append(r)
        self._starved = 0
        logger.info(
            "preempted request %s after %d generated tokens (%s, "
            "%d page refs reclaimed); re-queued with token carry",
            r.uid, len(s.outs), why, npages)

    # -- prompt-prefix sharing ------------------------------------------
    def _prefix_digest(self, r) -> str:
        """The (model, bucket, clipped-prompt) digest — always over the
        CLIPPED prompt (``prompt[-bucket:]``), so truncated requests hash
        what was actually prefilled and a preempt/resume cycle re-enters
        the index under the same key (never the raw prompt's stale
        hash)."""
        return prefix_cache.prefix_digest(r.prompt, self._bucket_of(r),
                                          self._prefix_salt)

    def _prefix_entry(self, r):
        """The publishable entry matching ``r``, or None.  A hit is only
        valid while the current width cap equals the donor's — under an
        unfrozen width policy the cold launch would have run capped
        differently, producing different masks and KV."""
        if self.prefix is None:
            return None
        e = self.prefix.lookup(self._prefix_digest(r))
        if e is None or e.width != self.eng._width_cap(e.bucket):
            return None
        return e

    def _publish_prefix(self, r, slot: int, logits, plan_row, stats,
                        plen: int, seq: int, width) -> None:
        """Publish a just-completed cold prefill into the prefix index:
        the slot's FULL page run (prompt pages + decode tail) is pinned
        with one shared reference per page, making it read-only — the
        donor's own next decode append COWs off its tail (the "first
        decode append into a shared page" boundary), and later identical
        prompts map the run instead of prefilling."""
        if self.prefix is None:
            return
        pages = np.array(self.slot_pages[slot], np.int32)
        entry = prefix_cache.PrefixEntry(
            digest=self._prefix_digest(r), bucket=seq, plen=plen,
            pages=pages, prompt_pages=seq // self.page_size,
            logits=logits, plan_row=plan_row, stats=dict(stats),
            width=width)
        self.prefix.publish(entry, self.alloc)

    def _cow_append_page(self, slot: int) -> None:
        """Copy-on-write at the decode boundary: this step appends KV at
        ``pos[slot]``; if the page holding that position is *shared*
        (refcount > 1 — the slot mapped it from the prefix index, or
        published it there), acquire a fresh page, copy the partial
        block, rewrite the slot's table entry, and drop the shared
        reference.  The other holders keep the original bit-for-bit.

        Pool pressure resolves in order: shed LRU index entries until a
        page frees (the index is a cache — under memory pressure it
        yields first); if the pool is genuinely exhausted, preempt THIS
        slot (pages reclaimed, tokens carried, bitwise replay on resume)
        rather than ever letting a live append land in a shared page."""
        b = int(self.pos[slot]) // self.page_size
        old = int(self.page_table[slot, b])
        if old == paged_cache.NULL_PAGE or self.alloc.refcount(old) <= 1:
            return
        fresh = self.alloc.acquire(1)
        while fresh is None and self.prefix is not None and len(self.prefix):
            self.prefix.evict_one(self.alloc)
            fresh = self.alloc.acquire(1)
        if fresh is None:
            self._preempt_slot(slot, "COW page exhaustion")
            return
        new = int(fresh[0])
        self.cache = paged_cache.copy_page(self.cache, old, new)
        self.page_table[slot, b] = new
        pages = self.slot_pages[slot]
        pages[pages == old] = new
        self.alloc.release([old])
        self._cow_copies += 1

    # -- adaptive pattern refresh ---------------------------------------
    def _init_refresh_slot(self, slot: int, row, pos: int) -> None:
        """Arm refresh bookkeeping for a just-admitted slot: a fresh
        recent-query ring (warm-up starts now — a preempt → resume cycle
        re-warms from scratch) and the host-side reference to the slot's
        spliced full-width row (tail accounting + horizon extensions)."""
        cfg = self.eng.model.cfg
        self.refresh[slot] = refresh_mod.make_refresh_state(
            cfg.num_layers, cfg.num_heads, cfg.resolved_head_dim,
            self.page_size, pos)
        self._slot_rows[slot] = row

    def _drop_refresh_slot(self, slot: int) -> None:
        """Discard a vacated/preempted slot's refresh state — the next
        occupant (or a resume of the same request) starts frozen with a
        cold query window."""
        self.refresh.pop(slot, None)
        self._slot_rows.pop(slot, None)

    def _slot_tail_stats(self, slot: int):
        """(tail_fraction, traffic_fraction) of the slot's current row,
        against its own page allocation."""
        row = self._slot_rows.get(slot)
        if row is None:
            return 0.0, 0.0
        return dplan.plan_row_tail_stats(
            row, prefill_blocks=int(self.pflens[slot]) // self.page_size,
            num_blocks=len(self.slot_pages.get(slot, ())) or None)

    def _refresh_fenced(self, slot: int) -> bool:
        """COW fence: refresh defers while any of the slot's pages is
        still shared (refcount > 1 — the slot is a prefix donor whose run
        the index pins, or a hit still riding mapped pages).

        A shared row's canonical pattern is the donor's published frozen
        row; re-estimating it mid-share would fork the keep-set away from
        what later hits replay while the physical pages are still being
        COW-remapped underneath.  Deferral ends once sharing does: written
        tail pages go private at their first COW, and the rest unpin when
        the index entry is evicted/shed.  Deferred refreshes are counted
        (``engine.refresh_stats["deferred_cow"]``), never dropped — the
        cadence check re-fires every block boundary."""
        for pg in self.slot_pages.get(slot, ()):
            if (int(pg) != paged_cache.NULL_PAGE
                    and self.alloc.refcount(int(pg)) > 1):
                return True
        return False

    def _horizon_guard(self) -> None:
        """Keep every refreshed row's dense horizon ahead of its append
        position — runs before each decode step's kernels.

        A refreshed row keeps only ``horizon_blocks`` of lookahead; if the
        slot is about to append past it (a refresh was deferred, or the
        cadence outlived the horizon), splice a cheap horizon *extension*
        (:func:`decode_plan.extend_plan_row_horizon` — no strip pass) so
        the appended block is visible to this step's attention.  Frozen
        rows (``horizon_end == 0``) keep their whole tail and never need
        this."""
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            st = self.refresh.get(i)
            if st is None or st.horizon_end <= 0:
                continue
            blk = int(self.pos[i]) // self.page_size
            if blk < st.horizon_end:
                continue
            alloc_blocks = (len(self.slot_pages.get(i, ()))
                            or self.table_blocks)
            hi = min(blk + 1 + self.horizon_blocks, alloc_blocks)
            row = dplan.extend_plan_row_horizon(
                self._slot_rows[i], st.horizon_end, hi)
            self._slot_rows[i] = row
            self._splice_row(i, row)
            st.horizon_end = hi
            st.extensions += 1
            self.eng.refresh_stats["horizon_extensions"] += 1

    def _refresh_tick(self) -> None:
        """Post-step refresh pass: re-estimate any occupied slot whose
        cadence is due (or whose row's dense-tail fraction crossed the
        early-refresh threshold) at a block-aligned position with a warm
        query window.  Mid-prefill chunked admissions never appear here —
        a slot is only occupied (``self.slots[i]``) once its final quantum
        completed and its row was spliced."""
        ecfg = self.eng.ecfg
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            st = self.refresh.get(i)
            if st is None:
                continue
            pos = int(self.pos[i])
            if not st.window_ready(pos):
                continue
            due = pos - st.last_refresh_pos >= ecfg.refresh_every
            if not due and ecfg.refresh_tail_threshold > 0:
                tf, _ = self._slot_tail_stats(i)
                due = tf >= ecfg.refresh_tail_threshold
            if not due:
                continue
            if self._refresh_fenced(i):
                st.deferred_cow += 1
                self.eng.refresh_stats["deferred_cow"] += 1
                continue
            self._refresh_slot(i, s, st, pos)

    def _refresh_slot(self, slot: int, s: _Slot, st, pos: int) -> None:
        """Re-estimate one slot's pattern from its live paged KV: strip
        kernel over the page-table prefix against the captured query
        window → per-head score-mass budgets → ragged keep-sets → a
        replacement row whose dense tail collapses to the bounded horizon
        — spliced through the same :meth:`_splice_row` path as
        admissions."""
        eng = self.eng
        ecfg = eng.ecfg
        bs = self.page_size
        nblk = pos // bs
        alloc_blocks = len(self.slot_pages.get(slot, ()))
        if nblk <= 0 or not alloc_blocks:
            return
        with tracing.phase(eng.phase_s, "refresh", "refresh",
                           uid=s.req.uid):
            horizon = max(min(self.horizon_blocks, alloc_blocks - nblk), 0)
            row = dplan.build_refresh_plan_row(
                jnp.asarray(st.window()), self.cache["stack"][0],
                jnp.asarray(self.page_table[slot]), eng.model.cfg,
                block_size=bs, num_blocks=nblk,
                table_blocks=self.table_blocks, horizon_blocks=horizon,
                mass=ecfg.refresh_mass, min_width=ecfg.refresh_min_width,
                strip_impl=ecfg.refresh_strip_impl)
            self._slot_rows[slot] = row
            self._splice_row(slot, row)
            st.last_refresh_pos = pos
            st.horizon_end = nblk + horizon
            r = s.req
            r.refreshes += 1
            eng.refresh_stats["refreshes"] += 1
            r.tail_fraction, r.plan_traffic_fraction = \
                dplan.plan_row_tail_stats(
                    row, prefill_blocks=int(self.pflens[slot]) // bs,
                    num_blocks=alloc_blocks)
            if r.pattern_stats is not None:
                r.pattern_stats["decode_traffic_fraction"] = \
                    r.plan_traffic_fraction

    def _admit(self) -> None:
        """WAITING → PREFILL: fill free slots from the arrival queue."""
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return
            r = self.queue[0]
            if self.paged and self._prefix_entry(r) is None:
                self._shed_index_for(r)
                if self.alloc.free_pages < self._pages_needed(r):
                    # pool exhausted: the head request stays WAITING until
                    # a finishing slot frees its pages (admission stays
                    # FIFO — later, smaller requests do not jump the
                    # queue); past the starvation window a decoding victim
                    # is preempted.  A prefix-cache hit skips the gate: it
                    # maps shared pages instead of acquiring, so headroom
                    # is not required.
                    self._note_starved(r)
                    return
            wait = (self.t0 + r.arrival_s) - time.time()
            if wait > 0:
                if any(s is not None for s in self.slots):
                    return              # keep decoding, admit it later
                self._idle_sleep(wait)  # fully idle: jump to next arrival
            self.queue.popleft()
            self._start(r, free[0])

    def _idle_sleep(self, wait: float) -> None:
        """Every slot idle and the queue head not yet due: sleep until it
        arrives."""
        with tracing.phase(self.eng.phase_s, "idle", "idle_sleep"):
            time.sleep(wait)

    def _start(self, r, slot: int) -> None:
        """PREFILL → DECODE: prefill one request alone (one-shot), sample
        its first token, splice its KV row and DecodePlan row into the live
        state."""
        eng, seq = self.eng, self._bucket_of(r)
        entry = self._prefix_entry(r)
        if entry is not None:
            self._start_from_prefix(r, slot, entry)
            return
        if self.prefix is not None:
            self.prefix.misses += 1
        self._starved = 0               # the head admitted: starvation over
        r.state = "prefilling"
        toks = np.zeros((1, seq), np.int32)
        plen = eng._pad_prompt(r, seq, toks[0])

        width = eng._width_cap(seq)
        r.queue_s = max(time.time() - (self.t0 + r.arrival_s), 0.0)
        launch = tracing.phase(eng.phase_s, "prefill", "prefill_launch",
                               uid=r.uid)
        try:
            # per-request prefill quarantine: an exception (or injected
            # fault) fails ONLY this request — no slot was occupied and no
            # pages granted yet, so nothing to unwind
            with launch:
                if self.faults is not None:
                    self.faults.check_prefill([r.uid])
                prefill = eng._prefill_fn(1, seq, width)
                result = prefill(eng.params, jnp.asarray(toks),
                                 jnp.asarray([plen], jnp.int32))
                jax.block_until_ready(result.last_logits)
                finite = bool(
                    np.isfinite(np.asarray(result.last_logits)).all())
        except Exception as e:          # noqa: BLE001 — quarantine wall
            r.prefill_s = launch.s
            err = (e if isinstance(e, RequestError) else RequestError(
                r.uid, f"prefill raised {type(e).__name__}: {e}",
                kind="prefill"))
            logger.warning("quarantined: %s", err)
            self._finish_inert(r, "failed", error=err)
            return
        r.prefill_s = launch.s
        if any(s is not None for s in self.slots):
            # the whole-sequence launch ran while other slots wanted to
            # decode — the interference chunked admission amortizes
            r.prefill_stall_s = r.prefill_s
        if not finite:
            err = RequestError(r.uid, "non-finite prefill logits",
                               kind="prefill")
            logger.warning("quarantined: %s", err)
            self._finish_inert(r, "failed", error=err)
            return

        stats = eng._record_prefill_stats(result, width, seq)
        r.pattern_stats = stats
        if result.expert_rows is not None:
            r.add_expert_rows(result.expert_rows)

        if r.max_new_tokens <= 0:       # prefill-only: no token is emitted
            self._finish(_Slot(req=r, key=jax.random.PRNGKey(0), outs=[],
                               last_tok=0, t_first=time.time()), "length")
            return

        # preemption carry: the prompt was re-prefilled at its ORIGINAL
        # bucket (bitwise the first admission), the key chain restarts
        # from the same fold_in, and the carried tokens are force-fed
        # through the decode steps — the resumed stream is the
        # unpreempted stream, bitwise
        carry = list(r.resume_tokens)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), r.uid)
        key, sub = jax.random.split(key)
        tok0 = int(sample_token(sub, result.last_logits, r.sampling)[0])
        if carry:
            tok0 = carry[0]             # carried tokens are verbatim
        t_first = time.time()
        if not carry:                   # TTFT is first-ever token only
            r.ttft_s = max(t_first - (self.t0 + r.arrival_s), 0.0)

        s = _Slot(req=r, key=key, outs=[tok0], last_tok=tok0,
                  t_first=t_first, replay=carry[1:], carry_len=len(carry))
        if r.sampling.is_stop(tok0):
            self._finish(s, "stop")
            return                      # slot stays free for the next admit
        if len(s.outs) >= r.max_new_tokens:
            self._finish(s, "length")
            return

        # DECODE: occupy the slot — KV row + plan row spliced in-flight
        # (the plan is built only now: a request that finished on its first
        # token never pays the O(L·Hkv·NB) table build)
        if self.cache is None:
            dt = jax.tree.leaves(result.cache)[0].dtype
            self.cache = (paged_cache.init_paged_pool(
                              eng.model.cfg, num_pages=self.num_pages,
                              page_size=self.page_size, dtype=dt)
                          if self.paged else
                          eng.model.init_cache(self.nslots, self.cache_len,
                                               dtype=dt))
        if self.paged:
            # _admit gated on headroom, so the grant always succeeds; the
            # prefill KV fills the first seq // page_size pages, the rest
            # are the decode tail the decode append grows into
            pages = self._alloc_slot_pages(slot, self._pages_needed(r))
            self.cache = paged_cache.insert_prefill(
                self.cache, result.cache, pages[: seq // self.page_size])
        else:
            self.cache = eng.cache_insert(self.cache, result.cache, slot)
        prow = None
        if self.use_sparse:
            # the row is built at the request's own allocation (its bucket
            # + the shared decode tail); under paging it is then padded to
            # the scheduler-wide table width so mixed buckets splice into
            # one fixed-shape plan
            alloc_len = seq + self.extra_len
            if result.sp_state is not None:
                rplan = dplan.build_decode_plan_auto(
                    eng.sp, result.sp_state, eng.model.cfg,
                    prefill_len=seq, cache_len=alloc_len)
            else:
                # no pattern dictionary came back for THIS admission → give
                # its slot the all-keep dense row; every other slot (and
                # every later admission) keeps sparse decode.  Replaces the
                # old sticky scheduler-wide use_sparse disable.
                rplan = dplan.dense_decode_plan(
                    eng.model.cfg, cache_len=alloc_len,
                    block_size=max(eng.sp.cfg.block_size, 1))
            stats.update(eng._plan_stats(rplan, alloc_len))
            r.tail_fraction, r.plan_traffic_fraction = \
                dplan.plan_row_tail_stats(
                    rplan, prefill_blocks=seq // self.page_size)
            if self.paged:
                rplan = dplan.pad_plan_row(rplan, self.table_blocks)
            self._splice_row(slot, rplan)
            self._stale_slots.discard(slot)    # refill replaced the row
            prow = rplan
        self.pos[slot] = seq
        self.plens[slot] = plen
        self.pflens[slot] = seq
        self.slots[slot] = s
        r.state = "decode"
        if self.refresh_on:
            self._init_refresh_slot(slot, prow, seq)
        self._publish_prefix(r, slot, result.last_logits, prow, stats,
                             plen, seq, width)

    def _start_from_prefix(self, r, slot: int, entry) -> None:
        """PREFIX HIT → DECODE: an identical (clipped) prompt was already
        prefilled this serve — map the donor's page run into this slot's
        table read-only (one shared reference per page; acquiring ZERO
        fresh pages), skip the prefill launch, and replay the donor's
        cached first-token logits and DecodePlan row.

        Bitwise the cold serve: the donor's launch and this request's
        hypothetical cold launch are the same deterministic compiled
        program on identical inputs (same clipped tokens, same bucket,
        same width cap — _prefix_entry refuses mismatched caps), and the
        sampling key chain derives from THIS request's uid exactly as a
        cold admission's would.  The width-policy observation is replayed
        too, so later buckets' cap evolution cannot diverge.  Decode
        appends land in the mapped (shared) tail pages only after the COW
        guard moves the slot onto fresh private copies."""
        eng, seq = self.eng, self._bucket_of(r)
        self._starved = 0               # the head admitted: starvation over
        r.state = "prefilling"
        # the hit never reaches _pad_prompt, so flag the clip here — the
        # digest already hashed the clipped tokens (that IS the hit)
        r.truncated = len(np.asarray(r.prompt)) > seq
        r.queue_s = max(time.time() - (self.t0 + r.arrival_s), 0.0)
        hit = tracing.phase(eng.phase_s, "prefill", "prefix_hit", uid=r.uid)
        try:
            # injected prefill faults still apply: a poisoned request
            # fails deterministically whether or not its prompt is cached
            with hit:
                if self.faults is not None:
                    self.faults.check_prefill([r.uid])
        except Exception as e:          # noqa: BLE001 — quarantine wall
            err = (e if isinstance(e, RequestError) else RequestError(
                r.uid, f"prefill raised {type(e).__name__}: {e}",
                kind="prefill"))
            logger.warning("quarantined: %s", err)
            self._finish_inert(r, "failed", error=err)
            return
        r.prefill_s = hit.s             # ≈ 0: the hit skips the launch
        r.prefix_hit = True
        entry.hits += 1
        self.prefix.hits += 1
        stats = eng._replay_prefill_stats(entry.stats, seq)
        r.pattern_stats = stats

        if r.max_new_tokens <= 0:       # prefill-only: no token is emitted
            self._finish(_Slot(req=r, key=jax.random.PRNGKey(0), outs=[],
                               last_tok=0, t_first=time.time()), "length")
            return

        # same carry/key contract as _start — tok0 comes from the donor's
        # cached last-prompt-token logits, which ARE this prompt's logits
        carry = list(r.resume_tokens)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), r.uid)
        key, sub = jax.random.split(key)
        tok0 = int(sample_token(sub, entry.logits, r.sampling)[0])
        if carry:
            tok0 = carry[0]             # carried tokens are verbatim
        t_first = time.time()
        if not carry:                   # TTFT is first-ever token only
            r.ttft_s = max(t_first - (self.t0 + r.arrival_s), 0.0)

        s = _Slot(req=r, key=key, outs=[tok0], last_tok=tok0,
                  t_first=t_first, replay=carry[1:], carry_len=len(carry))
        if r.sampling.is_stop(tok0):
            self._finish(s, "stop")
            return                      # no pages were mapped yet
        if len(s.outs) >= r.max_new_tokens:
            self._finish(s, "length")
            return

        # DECODE: map the donor's run — refcount++ on every page, table
        # row rewritten, zero pages acquired.  The run length always
        # matches (same bucket, scheduler-wide decode tail).
        if len(entry.pages) != self._pages_needed(r):
            raise RuntimeError("prefix entry geometry mismatch")
        self.prefix.pages_saved += len(entry.pages)
        self.alloc.share(entry.pages)
        self.slot_pages[slot] = np.array(entry.pages, np.int32)
        self.page_table[slot, : len(entry.pages)] = entry.pages
        if self.use_sparse:
            r.tail_fraction, r.plan_traffic_fraction = \
                dplan.plan_row_tail_stats(
                    entry.plan_row, prefill_blocks=seq // self.page_size,
                    num_blocks=(seq + self.extra_len) // self.page_size)
            self._splice_row(slot, entry.plan_row)
            self._stale_slots.discard(slot)
        self.pos[slot] = seq
        self.plens[slot] = entry.plen
        self.pflens[slot] = seq
        self.slots[slot] = s
        r.state = "decode"
        if self.refresh_on:
            self._init_refresh_slot(slot, entry.plan_row, seq)

    # -- chunked admission ----------------------------------------------
    def _pack_limit(self, seq: int) -> int:
        """Max prompts one chunked run may pack at segment length ``seq``.
        Packing concatenates segments on one masked grid, so it needs a
        mask-carrying prefill (the block-diagonal isolation mask has
        nowhere to go on the pure dense path), an applicable pattern config
        at the packed length, and no sliding window (whose width is
        measured on packed positions)."""
        eng = self.eng
        p = max(eng.ecfg.prefill_pack, 1)
        if p <= 1:
            return 1
        if eng.ecfg.method == "dense" or not eng.sp.cfg.enabled:
            return 1
        if eng.model.cfg.sliding_window:
            return 1
        if seq % max(eng.sp.cfg.block_size, 1):
            return 1
        while p > 1 and not eng.sp.applicable(seq * p):
            p -= 1
        return p

    def _assemble_run(self) -> Optional[ChunkedPrefillRun]:
        """Gather arrived queue heads into the next chunked run — one
        segment per free slot, up to the pack limit."""
        eng = self.eng
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return None
        head_hit = self._prefix_entry(self.queue[0]) is not None
        if (self.paged and not head_hit and self.alloc.free_pages
                < self._pages_needed(self.queue[0])):
            # same FIFO headroom gate as the one-shot path: the head stays
            # WAITING until a finishing slot frees its pages — or a victim
            # is preempted once the starvation window is exceeded.  A
            # prefix-cache hit maps shared pages instead of acquiring, so
            # it skips the gate.
            self._note_starved(self.queue[0])
            return None
        wait = (self.t0 + self.queue[0].arrival_s) - time.time()
        if wait > 0:
            if any(s is not None for s in self.slots):
                return None             # keep decoding, admit it later
            self._idle_sleep(wait)      # fully idle: jump to next arrival
        if head_hit:
            # a prefix-cache hit needs no chunked run at all — _start
            # routes it through the hit path (cached logits + mapped
            # pages); the loop assembles the next cold run next step
            self._start(self.queue.popleft(), free[0])
            return None

        seq = self._bucket_of(self.queue[0])
        chunk = self.chunk if not self.paged else eng._chunk_tokens(seq)
        if self.paged and not chunk:
            # this bucket has no chunk decomposition (e.g. smaller than one
            # quantum) — admit it one-shot and let the loop continue
            self._start(self.queue.popleft(), free[0])
            return None
        limit = min(self._pack_limit(seq), len(free))
        group, now = [], time.time()
        reserve = self.alloc.free_pages if self.paged else 0
        while (self.queue and len(group) < limit
               and (self.t0 + self.queue[0].arrival_s) <= now):
            if self.paged:
                r = self.queue[0]
                if self._bucket_of(r) != seq:
                    break       # packing needs one shared segment length
                if group and self._prefix_entry(r) is not None:
                    break       # a hit never rides a packed run — it is
                                # admitted launch-free next step instead
                need = self._pages_needed(r)
                if need > reserve:
                    break       # the rest of the group waits for headroom
                reserve -= need
            group.append(self.queue.popleft())
        if not group:
            return None
        if self.prefix is not None:
            self.prefix.misses += len(group)
        self._starved = 0               # the head admitted: starvation over
        for r in group:
            r.queue_s = max(now - (self.t0 + r.arrival_s), 0.0)
            r.state = "prefilling"
        # the width-policy observations cover the solo bucket geometry, not
        # the packed grid — packed runs prefill uncapped
        width = eng._width_cap(seq) if len(group) == 1 else None
        if self.paged:
            # pages are granted at assembly so the in-flight run's per-layer
            # KV inserts have somewhere to land; an early finish at
            # completion returns them
            for r, slot in zip(group, free):
                self._alloc_slot_pages(slot, self._pages_needed(r))
        self._run_wall = 0.0
        return ChunkedPrefillRun(eng, group, free[: len(group)], seq,
                                 chunk, width)

    def _prefill_step(self) -> None:
        """Advance admission by exactly ONE quantum (assembling a new run
        first if none is in flight): the chunked loop's prefill share of
        each scheduler step."""
        if self.run_ is None:
            with tracing.span("assemble_run"):
                self.run_ = self._assemble_run()
            if self.run_ is None:
                return
        run = self.run_
        phase_s = self.eng.phase_s
        uid = tracing.uid_arg(run.requests)
        occupied = any(s is not None for s in self.slots)
        quantum = tracing.phase(phase_s, "prefill", "prefill_quantum",
                                kind=run.quantum, layer=run.layer,
                                chunk=run.chunk_i, uid=uid)
        try:
            with quantum:
                if self.faults is not None:
                    # injected prefill faults land between quanta: a raised
                    # PrefillError quarantines the run; a SlowQuantum delay
                    # stretches the quantum so deadlines can expire it
                    self.faults.check_prefill([r.uid for r in run.requests])
                    d = self.faults.quantum_delay(
                        [r.uid for r in run.requests])
                    if d > 0:
                        time.sleep(d)
                ev = run.step()
        except Exception as e:          # noqa: BLE001 — quarantine wall
            self._quarantine_run(run, e)
            return
        dt = quantum.s
        self._run_wall += dt
        if occupied:
            # this quantum ran instead of a decode step: charge the stall
            # to the admitting request(s), split across packed segments
            share = dt / len(run.requests)
            for r in run.requests:
                r.prefill_stall_s += share
        if ev == "kv":
            pages = (run.P * run.seq // self.page_size if self.paged
                     else 0)
            with tracing.phase(phase_s, "prefill", "kv_insert",
                               layer=run.kv_layer, pages=pages, uid=uid):
                self._insert_kv(run)
        elif ev == "done":
            with tracing.phase(phase_s, "prefill", "complete_run", uid=uid):
                self._complete_run(run)
            self.run_ = None

    def _insert_kv(self, run: ChunkedPrefillRun) -> None:
        """Write the just-finalized layer's K/V into the admitted slot(s)
        — incremental insert, while the other slots keep decoding."""
        eng = self.eng
        if self.cache is None:
            dt = jax.tree.leaves(run.kv)[0].dtype
            self.cache = (paged_cache.init_paged_pool(
                              eng.model.cfg, num_pages=self.num_pages,
                              page_size=self.page_size, dtype=dt)
                          if self.paged else
                          eng.model.init_cache(self.nslots, self.cache_len,
                                               dtype=dt))
        if self.latent:             # MLA admits dense, so never packed
            pages = self.slot_pages[run.slot_ids[0]]
            self.cache = paged_cache.insert_latent_layer(
                self.cache, run.kv_layer, run.kv,
                pages[: run.seq // self.page_size])
            return
        k, v = run.kv
        for j, slot in enumerate(run.slot_ids):
            if self.paged:
                pages = self.slot_pages[slot][: run.seq // self.page_size]
                if run.P > 1:
                    self.cache = paged_cache.insert_prefill_layer(
                        self.cache, run.kv_layer, k, v, pages,
                        offset=j * run.seq, length=run.seq)
                else:
                    self.cache = paged_cache.insert_prefill_layer(
                        self.cache, run.kv_layer, k, v, pages)
            elif run.P > 1:
                self.cache = eng.cache_insert_layer(
                    self.cache, run.kv_layer, slot, k, v,
                    offset=j * self.seq, length=self.seq)
            else:
                self.cache = eng.cache_insert_layer(
                    self.cache, run.kv_layer, slot, k, v)

    def _plan_row(self, run: ChunkedPrefillRun, j: int):
        """Single-slot DecodePlan row for segment ``j`` of a finished run."""
        eng = self.eng
        cfg = eng.model.cfg
        # the row's geometry is the run's own allocation (identical to
        # self.cache_len in contiguous mode, where run.seq == self.seq)
        alloc_len = run.seq + self.extra_len
        if run.sp_state is None:
            # per-request dense fallback — same contract as _start
            return dplan.dense_decode_plan(
                cfg, cache_len=alloc_len,
                block_size=max(eng.sp.cfg.block_size, 1))
        if run.P > 1:
            keep = sparse_decode.packed_decode_keep_blocks(
                eng.sp, run.sp_state, cfg.num_layers, cfg.num_heads,
                num_segs=run.P, seg_blocks=run.seg_blocks, segment=j)
            return dplan.build_decode_plan(
                eng.sp, run.sp_state, cfg, prefill_len=run.seq,
                cache_len=alloc_len, keep_blocks=keep)
        return dplan.build_decode_plan_auto(
            eng.sp, run.sp_state, cfg, prefill_len=run.seq,
            cache_len=alloc_len)

    def _complete_run(self, run: ChunkedPrefillRun) -> None:
        """Final quantum done: sample each segment's first token, splice
        its DecodePlan row, and occupy its slot — the PREFILLING → DECODE
        transition of chunked admission.  (The KV rows are already in the
        cache, inserted layer by layer as the quanta completed.)"""
        eng, seq = self.eng, run.seq
        shim = types.SimpleNamespace(stats=run.attn_stats)
        stats = eng._record_prefill_stats(shim, run.width, seq)
        for j, (r, slot) in enumerate(zip(run.requests, run.slot_ids)):
            reason = self._doomed.pop(r.uid, None)
            if reason is not None:
                # cancelled / expired mid-prefill in a packed run whose
                # OTHER segments stayed live: the doomed segment never
                # occupies its slot; its pages return here
                if self.paged:
                    self._release_pages(slot)
                self._finish_inert(r, reason)
                continue
            r.prefill_s = self._run_wall
            rstats = dict(stats)
            r.pattern_stats = rstats
            if run.P == 1 and run.expert_rows is not None:
                r.add_expert_rows(run.expert_rows)

            if not bool(np.isfinite(np.asarray(run.logits[j])).all()):
                # per-segment quarantine at completion: this segment's
                # logits are poisoned but its neighbours' are usable
                if self.paged:
                    self._release_pages(slot)
                err = RequestError(r.uid, "non-finite prefill logits",
                                   kind="prefill")
                logger.warning("quarantined: %s", err)
                self._finish_inert(r, "failed", error=err)
                continue

            if r.max_new_tokens <= 0:   # prefill-only: no token is emitted
                if self.paged:
                    self._release_pages(slot)
                self._finish(_Slot(req=r, key=jax.random.PRNGKey(0),
                                   outs=[], last_tok=0,
                                   t_first=time.time()), "length")
                continue

            # preemption carry: same replay contract as _start — prompt
            # re-prefilled at its original bucket, carry force-fed
            carry = list(r.resume_tokens)
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed), r.uid)
            key, sub = jax.random.split(key)
            tok0 = int(sample_token(sub, run.logits[j: j + 1],
                                    r.sampling)[0])
            if carry:
                tok0 = carry[0]         # carried tokens are verbatim
            t_first = time.time()
            if not carry:               # TTFT is first-ever token only
                r.ttft_s = max(t_first - (self.t0 + r.arrival_s), 0.0)

            s = _Slot(req=r, key=key, outs=[tok0], last_tok=tok0,
                      t_first=t_first, replay=carry[1:],
                      carry_len=len(carry))
            if r.sampling.is_stop(tok0):
                if self.paged:
                    self._release_pages(slot)
                self._finish(s, "stop")
                continue                # slot stays free for the next run
            if len(s.outs) >= r.max_new_tokens:
                if self.paged:
                    self._release_pages(slot)
                self._finish(s, "length")
                continue

            prow = None
            if self.use_sparse:
                rplan = self._plan_row(run, j)
                rstats.update(eng._plan_stats(rplan, seq + self.extra_len))
                r.tail_fraction, r.plan_traffic_fraction = \
                    dplan.plan_row_tail_stats(
                        rplan, prefill_blocks=seq // self.page_size)
                if self.paged:
                    rplan = dplan.pad_plan_row(rplan, self.table_blocks)
                self._splice_row(slot, rplan)
                self._stale_slots.discard(slot)
                prow = rplan
            self.pos[slot] = seq
            self.plens[slot] = run.plens[j]
            self.pflens[slot] = seq
            self.slots[slot] = s
            r.state = "decode"
            if self.refresh_on:
                self._init_refresh_slot(slot, prow, seq)
            if run.P == 1:
                # packed (P > 1) segments are never published: their
                # logits/KV carry the pack-composition fusion delta
                # (greedy-exact but not bitwise vs a solo launch), and a
                # hit must replay the donor's SOLO cold behavior exactly
                self._publish_prefix(r, slot, run.logits[j: j + 1], prow,
                                     rstats, int(run.plens[j]), seq,
                                     run.width)

    # -- decode ----------------------------------------------------------
    def _decode_step(self) -> None:
        """One fixed-shape decode step over all slots (occupied or inert),
        then per-slot sampling, early exit, and slot freeing."""
        eng = self.eng
        if self.prefix is not None or self.refresh_on:
            with tracing.phase(eng.phase_s, "decode", "decode_guard"):
                self._decode_guard()
        occ = [i for i, s in enumerate(self.slots) if s is not None]
        eng.slot_steps += self.nslots
        eng.active_slot_steps += len(occ)
        with tracing.phase(eng.phase_s, "decode", "decode_step",
                           occupied=len(occ)):
            self._decode_occupied(occ)
        if self.refresh_on:
            self._refresh_tick()

    def _decode_guard(self) -> None:
        """Make every occupied slot safe to append before the step."""
        if self.prefix is not None:
            # COW guard at the decode boundary: every occupied slot about
            # to append into a shared page is moved onto a fresh private
            # copy first (or, on true pool exhaustion, preempted) — a
            # shared page is never written.  Runs before the step's
            # occupied slots are listed, so a COW-preempted slot sits this
            # step out.
            for i, s in enumerate(self.slots):
                if s is not None:
                    self._cow_append_page(i)
        if self.refresh_on:
            # a refreshed row's bounded horizon must always cover this
            # step's append block — extend it (cheaply, no strip pass)
            # before the kernels run
            self._horizon_guard()

    def _decode_occupied(self, occ: List[int]) -> None:
        """Launch the step for slots ``occ`` (the others decode inert),
        pull its logits, and sample each occupied slot's next token."""
        eng = self.eng
        with tracing.span("decode_args"):
            toks = np.zeros((self.nslots,), np.int32)
            for i in occ:
                toks[i] = self.slots[i].last_tok
            if self.paged:
                decode = eng._decode_fn_paged(
                    self.nslots, self.table_blocks, self.use_sparse,
                    collect_queries=self.refresh_on)
                args = (eng.params, jnp.asarray(toks)[:, None], self.cache,
                        jnp.asarray(self.page_table), jnp.asarray(self.pos),
                        jnp.asarray(self.plens), jnp.asarray(self.pflens))
            else:
                decode = eng._decode_fn(self.nslots, self.seq,
                                        self.cache_len, self.use_sparse)
                args = (eng.params, jnp.asarray(toks)[:, None], self.cache,
                        jnp.asarray(self.pos), jnp.asarray(self.plens))
        with tracing.span("decode_launch"):
            plan = (self.plan,) if self.use_sparse else ()
            logits, self.cache, *extra = decode(*args, *plan)
            qs = extra.pop(0) if self.refresh_on else None
            rows = extra.pop(0) if extra else None

        # one device→host sync for the whole step: greedy rows (the
        # conformance-critical common case) take np.argmax on the pulled
        # logits — same first-max-index rule as jnp.argmax, so tokens stay
        # bitwise equal to the legacy path — and only temperature-sampled
        # rows pay a per-slot device dispatch
        with tracing.span("decode_wait"):
            logits_h = np.asarray(logits)
            qs_h = None if qs is None else np.asarray(qs)
            if rows is not None:
                rows_h = np.asarray(rows)
                for i in occ:
                    self.slots[i].req.add_expert_rows(rows_h[i])
        with tracing.span("sample"):
            self._sample(occ, logits, logits_h, qs_h)

    def _sample(self, occ: List[int], logits, logits_h: np.ndarray,
                qs_h: Optional[np.ndarray]) -> None:
        """Per occupied slot: record its query (refresh), quarantine a
        non-finite row, take its next token, and vacate it at a stop token
        or its length."""
        if qs_h is not None:
            # ring up this step's post-rope queries (positions == current
            # self.pos, pre-increment) into each occupied slot's window
            for i in occ:
                st = self.refresh.get(i)
                if st is not None:
                    st.record(int(self.pos[i]), qs_h[:, i])
        for i in occ:
            self.pos[i] += 1            # this step wrote at the old pos
            s = self.slots[i]
            row = logits_h[i]
            if self.faults is not None:
                row = self.faults.corrupt_logits(s.req.uid, len(s.outs),
                                                 row)
            if not np.isfinite(row).all():
                # per-request fault quarantine: only this slot dies — the
                # decode rows share nothing across the batch axis, so
                # every other slot's tokens are bitwise-unaffected
                err = RequestError(s.req.uid, "non-finite decode logits",
                                   kind="decode")
                logger.warning("quarantined: %s", err)
                if s.req.error is None:
                    s.req.error = err
                self._vacate(i, s, "failed")
                continue
            if s.req.sampling.temperature <= 0.0:
                tok = int(np.argmax(row))
            else:
                s.key, sub = jax.random.split(s.key)
                tok = int(sample_token(sub, logits[i: i + 1],
                                       s.req.sampling)[0])
            if s.replay:
                # preemption carry: force the already-generated token (the
                # sampling above still ran, keeping the key chain aligned
                # for the post-replay stream)
                tok = s.replay.pop(0)
            s.outs.append(tok)
            s.last_tok = tok
            if s.req.sampling.is_stop(tok):
                self._vacate(i, s, "stop")
            elif len(s.outs) >= s.req.max_new_tokens:
                self._vacate(i, s, "length")

    def _vacate(self, slot: int, s: _Slot, reason: str) -> None:
        """Free a slot mid-decode: the request finalizes and the slot's
        plan row is marked stale — emptied before the next decode step
        unless a refill splices a new request's row in first.  Under
        paging the slot's pages return to the free list here: the inert
        slot's appends land in the null page (its table row is nulled) and
        its reads are masked, so recycling is immediate."""
        self.slots[slot] = None
        if self.paged:
            self._release_pages(slot)
        self._drop_refresh_slot(slot)
        if self.use_sparse:
            self._stale_slots.add(slot)
        self._finish(s, reason)

    # terminal Request.state per finish_reason (rejected requests never
    # reach the scheduler — listed for the shared vocabulary's sake)
    _TERMINAL_STATE = {"stop": "done", "length": "done",
                       "cancelled": "cancelled", "timeout": "cancelled",
                       "failed": "failed", "rejected": "failed"}

    def _finish(self, s: _Slot, reason: str) -> None:
        """→ {DONE, CANCELLED, FAILED}: finalize the request's output +
        real metrics and pin its terminal lifecycle state."""
        r = s.req
        now = time.time()
        r.output_tokens = np.asarray(s.outs, np.int32)
        r.finish_reason = reason
        r.state = self._TERMINAL_STATE.get(reason, "done")
        r.decode_s = max(now - s.t_first, 0.0)
        r.decode_tokens_per_s = self.eng._decode_rate(len(s.outs),
                                                      r.decode_s)
