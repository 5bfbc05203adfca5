"""Serving engine: slot-scheduled long-context inference with SharePrefill.

The engine mirrors the paper's deployment — **sparse prefill** (the paper's
contribution) followed by decode — and goes beyond it on two axes:

**Decode-phase pattern sharing.**  With ``decode_sparse=True`` the decode
phase reuses the prefill pattern dictionary through a
:class:`~repro.kernels.decode_attn.DecodePlan` built **once per batch**
(``repro.serving.decode_plan``), so every decode step streams only the
keep-set's kv blocks (paper §8 future work; decode is memory-bound per
EXPERIMENTS.md §Roofline).

**Continuous batching.**  With ``scheduler=True`` the transformer families
are served by the slot-based scheduler (``repro.serving.scheduler``)
instead of batch-at-a-time grouping: a persistent fixed-shape decode state
of ``max_batch`` slots with **per-slot positions** (each row decodes at its
own ``pos``), per-slot early exit on EOS / ``max_new_tokens``, and
immediate slot refill — a finished slot's KV row is overwritten by the next
request's freshly prefilled cache (:meth:`ServingEngine.cache_insert`, the
inverse of :meth:`ServingEngine.grow_cache`) and, under ``decode_sparse``,
its DecodePlan row is spliced in-flight
(``decode_plan.update_plan_slot`` / the Hkv-sharded variant) without
touching the other slots' tables.  Request lifecycle and per-request
metrics (queue time, TTFT, decode tokens/s) live in the scheduler; MLA
latent caches and the non-transformer families keep the legacy
batch-at-a-time path below (the dense carve-out — their caches have no
per-slot write layout).

**Step-cadence chunked admission.**  With ``prefill_chunk > 0`` the
scheduler stops running admissions as monolithic prefill launches (which
stall every occupied decode slot for the whole prefill) and instead drives
them as a sequence of small *quanta* (``repro.models.chunked_prefill`` via
:meth:`ServingEngine._chunk_fns`): per layer, a full-sequence mask-staging
quantum, one rectangular Q-chunk × full-KV attention launch per
``prefill_chunk`` tokens (the batched block-sparse kernel with
``q_block_offset``), and a full-sequence FFN/dictionary quantum.  The
engine interleaves at most one quantum with each decode step, writes the
admitting request's KV rows incrementally per layer
(:meth:`cache_insert_layer` — the partial-insert invariant: prefill writes
land in ``[0, seq)`` while inert-slot decode writes stay in the tail, so
in-flight rows never collide), and splices the DecodePlan row only once
the final quantum completes.  ``prefill_pack > 1`` additionally packs
several short queued prompts into one chunked run (per-segment positions +
a block-diagonal isolation mask; each segment lands in its own slot).
Quantum programs are cached per ``(total_len, width, seg_blocks)`` shape in
``_chunk_cache``, layer-indexed by a *traced* scalar so the cache stays
O(chunks), not O(layers × chunks).

**Block-paged KV cache.**  With ``paged=True`` decode state moves from one
contiguous ``(B, Hkv, S, hd)`` buffer per sequence bucket into a shared
page pool ``(L, num_pages, Hkv, page_size, hd)`` with a per-slot page
table (``repro.serving.paged_cache``; ``page_size == block_size``, page 0
reserved null).  The DecodePlan's block-index tables and the page tables
become *the same table* — a head's keep-set is its set of resident pages —
and the page-aware kernel twins (``flash_decode_plan_paged``,
``block_sparse_attention_batched_paged``, the Hkv-sharded
``sharded_flash_decode_paged``) translate only the K/V DMA address through
the scalar-prefetched table, staying bitwise-equal to the contiguous
kernels.  Admission allocates ``(bucket + decode_extra) / page_size``
pages (kept WAITING when the pool lacks headroom —
``pages_exhausted_steps`` counts the deferrals), prefill KV lands
page-at-a-time (whole-cache or per layer under chunked admission), the
decode append is a single in-place sliver scatter through the table
(retiring ``grow_cache`` reallocation and whole-row ``cache_insert``
copies on this path), and EOS/finish frees the slot's pages for reuse.
Because batch shape is now just page-table rows, the scheduler's
single-bucket restriction is lifted: ONE scheduler serves all requests,
and slots of different former buckets coexist in one decode batch (each
with its own per-slot ``prefill_len``), admission gated on pool headroom
rather than batch shape.

Requests are padded to a block multiple, grouped by sequence bucket
(contiguous mode) or admitted into one cross-bucket slot set (paged mode),
and served by two jitted programs (prefill, decode step) shared across
request shapes; the scheduler reuses the same compiled-program caches
(prefill at batch 1, decode at ``max_batch`` with vector ``pos``).

**Mesh-active routing:** serving inside a sharding-rules context whose
"model" axis is non-trivial (``distributed.sharding.active_model_mesh``)
runs both hot paths heads-sharded under ``shard_map`` — sparse prefill via
``resolve_attention_fn("sparse")`` and sparse decode via
``attention_decode`` → ``sharded_flash_decode`` — with the DecodePlan
tables built per kv-head shard (``decode_plan.build_decode_plan_auto``)
and spliced per shard (``decode_plan.update_plan_slot_auto``).  Outputs
are bitwise-identical to the unmeshed serve; the compiled-program caches
key on the rules-context identity.

For the transformer families, per-request prompt lengths are threaded into
prefill (last-logits gathered at each row's real last token, so the first
sampled token never conditions on right-pad) and, for GQA caches, into
decode as slot-validity so right-pad K/V is never attended (MLA latent
caches and the non-transformer families keep the plain length mask);
sampling honours each request's own :class:`SamplingConfig`, including
``stop_tokens`` (EOS) in both serving paths.  Prompts longer than the
largest bucket are clipped to its tail — ``Request.truncated`` flags it
and a warning is logged (``Request.allow_truncation=False`` turns the
clip into a validation rejection).  ``width_policy="count"`` resolves the
sparse kernel's static block budget W from observed row populations, so
the batched kernel's ragged grid issues steps proportional to *kept*
blocks.

**Request lifecycle (hardened).**  Every request walks the state machine

    WAITING → PREFILLING → DECODE → {DONE, FAILED, CANCELLED}

with a PREEMPTED → WAITING back-edge, tracked in ``Request.state``:

* **Validation** (:meth:`ServingEngine.validate_request`, run by
  ``serve()`` before any scheduling): empty/non-1D/non-integer prompts,
  negative ``max_new_tokens`` (0 stays the documented prefill-only
  contract), a prompt longer than the largest bucket with
  ``allow_truncation=False``, malformed ``stop_tokens``, and negative
  deadlines are rejected with a typed
  :class:`~repro.serving.errors.RequestError` carrying the uid
  (``finish_reason="rejected"``) instead of surfacing jnp shape errors
  from inside the fused batch.
* **Deadlines & cancellation**: ``Request.deadline_s`` (wall budget from
  arrival) and :class:`~repro.serving.scheduler.SchedulerHandle`
  (``serve(handle=...)``) terminate WAITING or DECODE requests with
  ``finish_reason="timeout"``/``"cancelled"``, freeing pages and splicing
  empty DecodePlan rows immediately; an in-flight chunked admission
  aborts cleanly between quanta.
* **Preemption with page reclaim** (``preempt_after_steps``): pool-starved
  admission evicts the lowest-priority decoding victim, frees its pages,
  and re-enqueues it WAITING with its generated tokens carried in
  ``Request.resume_tokens`` — a later admission re-prefills the original
  prompt (bitwise the first admission) and replays the carry through
  decode steps as forced tokens, so the resumed stream reproduces the
  unpreempted serve bitwise.
* **Fault quarantine**: a per-row isfinite guard on decode logits plus
  try/except isolation around per-request admission prefill marks only
  the offending request FAILED (``finish_reason="failed"``, the
  ``RequestError`` in ``Request.error``), vacates its slot and keeps every
  other slot's tokens bitwise-unaffected.  ``serve(faults=...)`` accepts a
  :class:`~repro.serving.faults.FaultInjector` for deterministic chaos
  testing.

The legacy batch path ignores handles, faults, deadlines, and preemption
(it has no step loop to reap from) — the hardened lifecycle is a scheduler
feature, like the rest of continuous batching.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.api import SharePrefill
from repro.distributed.sharding import current_rules
from repro.models.api import Model
from repro.serving import cache_ops
from repro.serving import decode_plan as dplan
from repro.serving.errors import RequestError
from repro.serving.sampling import SamplingConfig, sample_token
from repro.serving.width_policy import auto_width_cap, population_width_cap

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 16
    sampling: SamplingConfig = dataclasses.field(
        default_factory=SamplingConfig)
    arrival_s: float = 0.0              # simulated arrival offset from the
                                        # start of serve() (scheduler honours
                                        # it for admission; the legacy batch
                                        # path only uses it for metrics)
    deadline_s: float = 0.0             # wall budget from arrival (0 = none);
                                        # exceeded → finish_reason "timeout",
                                        # WAITING or DECODE alike (scheduler)
    priority: int = 0                   # preemption victim order: lower
                                        # priority is evicted first (ties →
                                        # fewest generated tokens)
    allow_truncation: bool = True       # False: a prompt longer than the
                                        # largest bucket is REJECTED at
                                        # validation instead of clipped
    # filled by the engine:
    output_tokens: Optional[np.ndarray] = None
    prefill_s: float = 0.0              # this request's own prefill wall
    decode_s: float = 0.0               # first token → last token wall
    queue_s: float = 0.0                # arrival → prefill start
    ttft_s: float = 0.0                 # arrival → first token
    decode_tokens_per_s: float = 0.0    # (n_tokens - 1) / decode_s
    prefill_stall_s: float = 0.0        # decode wall time other slots lost
                                        # to THIS request's admission (its
                                        # prefill wall while ≥1 slot was
                                        # occupied; a packed run's stall is
                                        # split across its segments)
    truncated: bool = False             # prompt clipped to the largest bucket
    finish_reason: str = ""             # "stop" (EOS) | "length" | "timeout"
                                        # | "cancelled" | "failed" (runtime
                                        # quarantine) | "rejected" (validation)
    state: str = "waiting"              # lifecycle: waiting | prefilling |
                                        # decode | done | cancelled | failed
    error: Optional[Exception] = None   # the typed RequestError behind a
                                        # failed / rejected terminal state
    waiting_deferred_steps: int = 0     # scheduler steps this request's
                                        # admission was deferred on pool
                                        # headroom — per-request starvation,
                                        # not just the engine-wide counter
    preempted_count: int = 0            # times evicted mid-decode (pages
                                        # reclaimed) and re-queued WAITING
    prefix_hit: bool = False            # admission hit the prompt-prefix
                                        # index: pages mapped read-only from
                                        # a donor's published run, prefill
                                        # launch skipped (bitwise the cold
                                        # serve; COW at the decode boundary)
    tail_fraction: float = 0.0          # share of this request's plan-row
                                        # streamed blocks lying in the dense
                                        # decode tail (past the prefill
                                        # region) — the staleness signal a
                                        # frozen row accretes and a refresh
                                        # collapses; last spliced row's value
    plan_traffic_fraction: float = 0.0  # this request's own plan-row
                                        # streamed-block fraction vs dense
                                        # (last spliced row's value)
    refreshes: int = 0                  # pattern refreshes this request's
                                        # slot received during decode
    # preemption carry (scheduler-internal): tokens generated before the
    # eviction, replayed through decode as forced tokens after the resume
    # re-prefills the original prompt
    resume_tokens: List[int] = dataclasses.field(default_factory=list)
    pattern_stats: Optional[Dict[str, float]] = None

    def metrics(self) -> Dict[str, float]:
        """Per-request serving metrics as one dict — the launcher summary
        and benches consume this; starvation and preemption are visible
        per request (``waiting_deferred_steps`` / ``preempted_count``)."""
        return {
            "queue_s": self.queue_s,
            "ttft_s": self.ttft_s,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "decode_tokens_per_s": self.decode_tokens_per_s,
            "prefill_stall_s": self.prefill_stall_s,
            "waiting_deferred_steps": self.waiting_deferred_steps,
            "preempted_count": self.preempted_count,
            "prefix_hit": float(self.prefix_hit),
            "tail_fraction": self.tail_fraction,
            "plan_traffic_fraction": self.plan_traffic_fraction,
            "refreshes": float(self.refreshes),
        }


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8
    method: str = "share"               # prefill pattern policy
    # "auto": sparse kernel on TPU, chunked elsewhere (resolved by
    # repro.models.attention.resolve_attention_fn)
    attn_impl: str = "auto"
    seq_buckets: tuple = (512, 2048, 8192, 32768)
    decode_extra: int = 128             # decode headroom beyond the prompt
    decode_sparse: bool = False         # decode-phase pattern sharing
                                        # (beyond-paper; needs method=share)
    # "auto": compiled flash-decode kernel on TPU, grouped einsum elsewhere
    # (resolved by repro.kernels.decode_attn.resolve_decode_impl)
    decode_impl: str = "auto"
    # static per-row block budget W for the sparse prefill kernel
    # (transformer families only; ignored for ssm/hybrid/encdec):
    #   width_policy="off"   → prefill_width (None = uncapped)
    #   width_policy="auto"  → density-percentile heuristic over the block
    #     densities observed on earlier batches of the same bucket
    #     (repro.serving.width_policy); first batch runs uncapped, then the
    #     cap freezes per bucket (a drifting W would recompile per batch).
    #   width_policy="count" → count-aware: W covers the largest observed
    #     (head, q-block) row population (× width_safety) of earlier batches
    #     of the bucket, so the batched kernel's ragged grid issues steps
    #     proportional to kept blocks instead of the NBkv rectangle while
    #     staying lossless for observed traffic.  Same uncapped-warmup /
    #     freeze-per-bucket lifecycle as "auto".
    prefill_width: Optional[int] = None
    width_policy: str = "off"           # "off" | "auto" | "count"
    width_percentile: float = 95.0
    width_safety: float = 1.25
    # slot-based continuous batching (repro.serving.scheduler): per-slot
    # decode positions, EOS early exit, immediate slot refill with in-flight
    # cache/DecodePlan splicing.  Transformer families only — MLA and the
    # non-transformer caches fall back to the legacy batch-at-a-time path.
    scheduler: bool = False
    # step-cadence chunked admission (tokens per prefill quantum, rounded up
    # to the pattern block size; 0 = whole-sequence one-shot admission).
    # Only takes effect under the scheduler on layouts with a chunkable
    # prefill (Model.prefill_chunk) — see ServingEngine._chunk_tokens.
    prefill_chunk: int = 0
    # multi-prompt prefill packing: concatenate up to this many same-bucket
    # queued prompts into one chunked run (per-segment positions + block-
    # diagonal isolation mask; each segment lands in its own slot).  1 = no
    # packing.  Requires a masked prefill path (method != "dense", pattern
    # sharing applicable, no sliding window) — unpackable runs fall back to
    # one prompt per run.
    prefill_pack: int = 1
    # block-paged KV cache (repro.serving.paged_cache): decode state in a
    # shared page pool + per-slot page tables (page_size == block_size), ONE
    # cross-bucket scheduler over all requests, admission gated on pool
    # headroom.  Implies the scheduler; falls back to the legacy path on the
    # non-scheduler families (MLA / ssm / hybrid / encdec).
    paged: bool = False
    # page-pool capacity (pages, including the reserved null page 0);
    # 0 = auto-size so the pool can never run out for max_batch slots.
    # Undersized pools keep requests WAITING (pages_exhausted_steps counts
    # the deferred admissions) — never a crash or a truncation.
    num_pages: int = 0
    # preemption with page reclaim (paged scheduler only): once the head of
    # the WAITING queue has been deferred on pool headroom for more than
    # this many consecutive scheduler steps, evict the lowest-priority
    # decoding victim (fewest generated tokens by default), free its pages,
    # and re-enqueue it WAITING with its generated tokens carried — a later
    # admission re-prefills the ORIGINAL prompt at its original bucket and
    # replays the carry through decode as forced tokens, so the resumed
    # stream reproduces the unpreempted one bitwise (greedy or sampled).
    # 0 disables preemption: undersized pools then defer admission
    # indefinitely (the pre-hardening behavior some tests pin).
    preempt_after_steps: int = 0
    # prompt-prefix sharing (paged scheduler only): a completed prefill
    # publishes its page run into an in-serve LRU index keyed on
    # (model, bucket, digest of the block-aligned CLIPPED prompt); a later
    # identical prompt maps the pages read-only (refcount++ per page —
    # acquiring ZERO fresh pool pages), skips its prefill launch entirely,
    # and replays the donor's cached first-token logits + DecodePlan row.
    # Bitwise-invisible: the donor's launch and the hit's hypothetical
    # cold launch are the same deterministic program on identical inputs,
    # and the sampling key chain derives from the hit's own uid — greedy
    # or sampled.  Published runs are read-only; the scheduler's COW guard
    # moves any writer (donor included) onto a fresh page at the decode
    # boundary.  (Caveat: with prefill_pack > 1 and temperature > 0,
    # sharing can re-compose packed runs, shifting OTHER requests' logits
    # by the pack-fusion delta — greedy streams are unaffected, the same
    # guarantee packing itself ships with.)
    prefix_sharing: bool = False
    # LRU capacity of the prefix index (entries; each pins its page run
    # until evicted — under pool pressure the index sheds entries first)
    prefix_max_entries: int = 32
    # adaptive pattern refresh during long decode (paged + decode_sparse
    # only): every ``refresh_every`` decode steps — or sooner, when a
    # slot's plan-row dense-tail fraction crosses
    # ``refresh_tail_threshold`` — the scheduler re-estimates that slot's
    # pattern from its live paged KV (the Pallas strip kernel over the
    # page pool against the slot's captured recent-query window), converts
    # the scores to genuinely ragged per-head keep-sets via cumulative
    # score-mass budgets (``refresh_mass``), and splices the refreshed row
    # in-flight, collapsing the frozen row's unbounded dense tail to a
    # bounded horizon of upcoming blocks.  0 disables refresh entirely:
    # the default-off serve is bitwise-identical to the pre-refresh
    # engine (same compiled programs, same plan widths, same tokens).
    refresh_every: int = 0
    # cumulative attention-mass coverage each head's keep-set must reach
    # (per-head budget = smallest k whose top-k strip mass ≥ this)
    refresh_mass: float = 0.95
    # early-refresh trigger: refresh a slot once its row's dense-tail
    # fraction (share of streamed blocks past the prefill region) crosses
    # this, even before the cadence is due.  0 disables the trigger.
    refresh_tail_threshold: float = 0.0
    # floor on every head's refreshed keep-set width (blocks)
    refresh_min_width: int = 1
    # dense lookahead blocks a refreshed row force-keeps for upcoming
    # appends; 0 = auto (refresh_every // block_size + 1, so appends
    # between refreshes always land in kept blocks)
    refresh_horizon_blocks: int = 0
    # strip-kernel impl for re-estimation ("auto" | "pallas" | "jnp")
    refresh_strip_impl: str = "auto"


class ServingEngine:
    def __init__(self, model: Model, params, sp: SharePrefill,
                 ecfg: EngineConfig = EngineConfig()):
        self.model = model
        self.params = params
        self.sp = sp
        self.ecfg = ecfg
        self._prefill_cache: Dict[Any, Callable] = {}
        self._decode_cache: Dict[Any, Callable] = {}
        self._chunk_cache: Dict[Any, Dict[str, Callable]] = {}
        self._density_obs: Dict[int, List[float]] = {}
        self._pop_obs: Dict[int, List[float]] = {}   # max_row_pop per batch
        self._width_frozen: Dict[int, Optional[int]] = {}
        # slot-occupancy accounting, reset per serve(): every decode step
        # contributes max_batch slot-steps of capacity and however many rows
        # were actually still emitting tokens (both serving paths update it)
        self.slot_steps = 0
        self.active_slot_steps = 0
        # per-phase wall-time accounting, reset per serve(): where the
        # scheduler's step loop spent its time (admission quanta vs decode
        # steps vs idle sleeps) — the observable that makes admission
        # interference measurable instead of inferred
        self.phase_s: Dict[str, float] = {"prefill": 0.0, "decode": 0.0,
                                          "idle": 0.0, "refresh": 0.0}
        # the slowest scheduler step of the serve: its seconds, step
        # number, the phase_s key that took most of it and its wall by
        # phase_s key (tracing.step)
        self.slowest_step: Dict[str, Any] = {"s": 0.0, "step": 0,
                                             "phase": "", "phase_s": {}}
        # paged-cache accounting, reset per serve(): admissions deferred on
        # pool headroom, and the pool's capacity/peak/utilization summary
        # (filled by the paged scheduler)
        self.pages_exhausted_steps = 0
        self.page_pool_stats: Dict[str, float] = {}
        # prefix-sharing accounting, reset per serve(): hit/miss/pages-
        # saved counters the paged scheduler publishes at end of serve
        self.prefix_stats: Dict[str, float] = {}
        # lifecycle hardening, set per serve(): the caller's cancellation
        # handle, the fault injector (chaos harness), and the number of
        # pool-starvation preemptions the scheduler performed
        self.handle = None
        self.faults = None
        self.preemptions = 0
        # adaptive pattern refresh accounting, reset per serve(): rows
        # re-estimated, refreshes deferred on shared (COW-pending) pages,
        # and cheap horizon extensions spliced without a strip pass
        self.refresh_stats: Dict[str, float] = {
            "refreshes": 0, "deferred_cow": 0, "horizon_extensions": 0}

    def slot_occupancy(self) -> float:
        """Mean fraction of decode slot capacity doing useful work during
        the last :meth:`serve` (1.0 = every slot emitted a token on every
        decode step)."""
        return (self.active_slot_steps / self.slot_steps
                if self.slot_steps else 0.0)

    # -- compiled-program management ------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.ecfg.seq_buckets:
            if n <= b:
                return b
        return self.ecfg.seq_buckets[-1]

    def _transformer_family(self) -> bool:
        """The transformer-family prefill lambdas accept attn_width and
        prompt_lens (ragged last-logits); ssm/hybrid/encdec do not."""
        return self.model.cfg.family in ("dense", "vlm", "moe")

    # back-compat alias
    _supports_prefill_width = _transformer_family

    def _width_cap(self, seq: int) -> Optional[int]:
        """Resolve the sparse-prefill block budget W for this bucket.

        Under the auto policy the cap is resolved once per bucket (from the
        densities observed up to that point) and then frozen — a drifting W
        would recompile the prefill program on every oscillation.  A cap of
        NB is uncapped in disguise; it resolves to None so no redundant
        capped program is compiled.
        """
        if not self._supports_prefill_width():
            return None
        if self.ecfg.width_policy not in ("auto", "count"):
            return self.ecfg.prefill_width
        if seq in self._width_frozen:
            return self._width_frozen[seq]
        obs = (self._density_obs if self.ecfg.width_policy == "auto"
               else self._pop_obs).get(seq)
        if not obs:
            # genuinely uncapped warmup — a prefill_width cap here would
            # bias the observations the heuristic is about to use
            return None
        nb = max(seq // max(self.sp.cfg.block_size, 1), 1)
        if self.ecfg.width_policy == "auto":
            w = auto_width_cap(obs, nb,
                               percentile=self.ecfg.width_percentile,
                               safety=self.ecfg.width_safety)
        else:
            # count-aware: each observation is already a per-batch max row
            # population, so cover the largest one (percentile 100)
            w = population_width_cap(obs, nb,
                                     safety=self.ecfg.width_safety)
        self._width_frozen[seq] = None if w >= nb else w
        return self._width_frozen[seq]

    def _prefill_fn(self, batch: int, seq: int, width: Optional[int] = None):
        """Jitted prefill program for one (batch, seq, width) shape.

        For transformer families the program takes per-request prompt
        lengths and gathers each row's last logits at ``prompt_len - 1`` —
        the first sampled token is conditioned on the prompt's real last
        token, never on right-pad."""
        ragged = self._transformer_family()
        # the sharding-rules context shapes the traced program (shard()
        # constraints on any axis, plus the mesh-active shard_map routing —
        # distributed.sharding.active_model_mesh), so the compiled-program
        # cache keys on the rules object itself (None when unmeshed): a
        # program traced under one context is never replayed under a
        # different one, including data-parallel-only or overridden rules
        key = (batch, seq, width, ragged, current_rules())
        if key not in self._prefill_cache:
            kwargs = {} if width is None else {"attn_width": width}

            if ragged:
                def prefill_step(params, tokens, plens):
                    return self.model.prefill(
                        params, tokens, self.sp, method=self.ecfg.method,
                        attn_impl=self.ecfg.attn_impl, prompt_lens=plens,
                        **kwargs)
            else:
                def prefill_step(params, tokens, plens):
                    del plens
                    return self.model.prefill(
                        params, tokens, self.sp, method=self.ecfg.method,
                        attn_impl=self.ecfg.attn_impl, **kwargs)
            self._prefill_cache[key] = jax.jit(prefill_step)
        return self._prefill_cache[key]

    def _decode_fn(self, batch: int, seq: int, cache_len: int,
                   sparse: bool = False):
        # only the non-MLA transformer families consume per-request length
        # masks / decode plans; MLA's latent-cache decode and the other
        # families keep the plain length-mask signature (pads attended —
        # the remaining documented simplification for those caches).
        # Mesh-active decode routing: when the serve runs inside a
        # sharding-rules context with a non-trivial "model" axis, the jitted
        # sparse step traces through distributed.sharding.
        # sharded_flash_decode (per-shard tables under shard_map) instead of
        # the single-device flash_decode_plan — resolved automatically at
        # trace time by attention_decode, mirroring prefill's
        # resolve_attention_fn("sparse") routing, so the cache key carries
        # the rules-context identity (same rationale as _prefill_fn).
        thread_lens = (self._transformer_family()
                       and not self.model.cfg.mla.enabled)
        key = (batch, seq, cache_len, sparse, thread_lens,
               current_rules())
        if key not in self._decode_cache:
            if sparse:
                # the jitted step consumes the prebuilt DecodePlan tables —
                # O(L·B·Hkv·NB) — never a token-level keep mask
                def decode_step(params, token, cache, pos, plens, plan):
                    return self.model.decode(
                        params, token, cache, pos, plan=plan,
                        prompt_lens=plens, prefill_len=seq,
                        decode_impl=self.ecfg.decode_impl)
            elif thread_lens:
                def decode_step(params, token, cache, pos, plens):
                    return self.model.decode(
                        params, token, cache, pos,
                        prompt_lens=plens, prefill_len=seq)
            else:
                def decode_step(params, token, cache, pos, plens):
                    del plens
                    return self.model.decode(params, token, cache, pos)
            self._decode_cache[key] = jax.jit(decode_step)
        return self._decode_cache[key]

    def _decode_fn_paged(self, batch: int, table_blocks: int,
                         sparse: bool = False, *,
                         collect_queries: bool = False):
        """Jitted decode step over the block-paged pool.

        The cache operand is the shared ``(L, P, Hkv, ps, hd)`` pool; batch
        geometry lives entirely in the ``(batch, table_blocks)`` page table
        and the per-slot ``pos``/``prompt_lens``/``prefill_lens`` vectors,
        so ONE compiled program serves every bucket mix — the paged
        scheduler never recompiles on cross-bucket churn.

        ``collect_queries`` compiles the refresh-mode twin (sparse only):
        the same step additionally returns the per-layer post-rope decode
        queries ``(L, B, H, hd)`` the scheduler rings up into each slot's
        recent-query window for strip re-estimation.  It is a separate
        cache entry — the default-off serve keeps replaying the exact
        2-output program it always compiled."""
        key = ("paged_q" if collect_queries else "paged", batch,
               table_blocks, sparse, current_rules())
        if key not in self._decode_cache:
            if sparse and collect_queries:
                def decode_step(params, token, cache, page_table, pos,
                                plens, pflens, plan):
                    return self.model.decode(
                        params, token, cache, pos, plan=plan,
                        prompt_lens=plens, prefill_len=pflens,
                        page_table=page_table,
                        decode_impl=self.ecfg.decode_impl,
                        collect_queries=True)
            elif sparse:
                def decode_step(params, token, cache, page_table, pos,
                                plens, pflens, plan):
                    return self.model.decode(
                        params, token, cache, pos, plan=plan,
                        prompt_lens=plens, prefill_len=pflens,
                        page_table=page_table,
                        decode_impl=self.ecfg.decode_impl)
            else:
                if collect_queries:
                    raise ValueError(
                        "collect_queries needs the sparse paged step "
                        "(refresh implies decode_sparse)")
                def decode_step(params, token, cache, page_table, pos,
                                plens, pflens):
                    return self.model.decode(
                        params, token, cache, pos, prompt_lens=plens,
                        prefill_len=pflens, page_table=page_table)
            self._decode_cache[key] = jax.jit(decode_step)
        return self._decode_cache[key]

    def _chunk_tokens(self, seq: int) -> int:
        """Resolve the admission chunk size (tokens per prefill quantum) for
        a bucket — 0 means one-shot admission.

        Chunked admission needs the quantum decomposition the transformer
        families expose (``Model.prefill_chunk``), a chunk-capable attention
        impl (the batched sparse kernel or the dense chunked path — the
        single-sample ``ref``/``kernel`` validation pins have no rectangular
        launch), a block-aligned bucket, and a single-device serve (the
        quanta are not mesh-routed).  Anything else falls back to the
        one-shot path, same numerics as before.
        """
        c = self.ecfg.prefill_chunk
        if c <= 0 or not self._supports_scheduler():
            return 0
        if self.model.prefill_chunk is None:
            return 0
        from repro.models.attention import resolved_attn_impl
        if resolved_attn_impl(self.ecfg.attn_impl) not in ("chunked",
                                                           "sparse"):
            return 0
        from repro.distributed.sharding import active_model_mesh
        if active_model_mesh() is not None:
            return 0
        bs = min(self.sp.cfg.block_size if self.sp.cfg.enabled else 128, seq)
        if seq % bs:
            return 0
        c = max(((c + bs - 1) // bs) * bs, bs)
        return min(c, seq)

    def _chunk_fns(self, total: int, width: Optional[int],
                   seg_blocks: Optional[int]) -> Dict[str, Callable]:
        """Jitted quantum programs for one (packed) admission shape.

        Keyed by ``(total_len, width, seg_blocks, rules)`` — NOT by layer:
        every quantum takes the full stacked params plus a *traced* layer
        index (``models.chunked_prefill._layer_params`` slices in-graph), so
        one compiled program per quantum kind serves every layer and the
        cache stays O(chunks) programs per shape.
        """
        key = (total, width, seg_blocks, current_rules())
        if key not in self._chunk_cache:
            api = self.model.prefill_chunk
            sp = self.sp
            method, impl = self.ecfg.method, self.ecfg.attn_impl

            def layer_begin(params, li, x, positions, sp_state, cluster_arr):
                return api.layer_begin(params, li, x, positions, sp,
                                       sp_state, cluster_arr, method=method,
                                       attn_impl=impl, seg_blocks=seg_blocks)

            import functools

            @functools.partial(jax.jit,
                               static_argnames=("chunk_start",
                                                "chunk_blocks"))
            def attn(q, k, v, masks, gate, perm, *, chunk_start,
                     chunk_blocks):
                return api.attn(sp, q, k, v, masks, gate, perm,
                                method=method, attn_impl=impl,
                                attn_width=width, chunk_start=chunk_start,
                                chunk_blocks=chunk_blocks)

            def layer_end(params, li, x, outs, k, v, ats, masks, decision,
                          sp_state, cluster_arr):
                out = (jnp.concatenate(outs, axis=2) if len(outs) > 1
                       else outs[0])
                at = None
                if ats is not None:
                    at = (jnp.concatenate(ats, axis=2) if len(ats) > 1
                          else ats[0])
                return api.layer_end(params, li, x, out, k, v, at, masks,
                                     decision, sp, sp_state, cluster_arr,
                                     method=method)

            self._chunk_cache[key] = {
                "begin": jax.jit(api.begin),
                "layer_begin": jax.jit(layer_begin),
                "attn": attn,
                "layer_end": jax.jit(layer_end),
                "finish": jax.jit(api.finish),
            }
        return self._chunk_cache[key]

    # -- serving ----------------------------------------------------------
    def validate_request(self, r: Request) -> None:
        """Reject a malformed request up front with a typed
        :class:`RequestError` carrying its uid — the submit-time half of
        fault isolation (a bad prompt shape or stop-token list must never
        surface as a jnp error from inside the fused batch).

        Checks: non-empty 1-D integer prompt; ``max_new_tokens >= 0``
        (0 stays the documented prefill-only contract — only *negative*
        budgets are malformed); ``deadline_s >= 0``; a prompt longer than
        the largest bucket needs ``allow_truncation`` (the default clips
        with a warning); ``stop_tokens`` must be non-negative ints."""
        p = np.asarray(r.prompt)
        if p.ndim != 1 or p.size == 0:
            raise RequestError(
                r.uid, f"prompt must be a non-empty 1-D token array "
                f"(got shape {p.shape})")
        if not np.issubdtype(p.dtype, np.integer):
            raise RequestError(
                r.uid, f"prompt dtype {p.dtype} is not an integer type")
        if r.max_new_tokens < 0:
            raise RequestError(
                r.uid, f"max_new_tokens={r.max_new_tokens} is negative "
                "(0 means prefill-only)")
        if r.deadline_s < 0:
            raise RequestError(r.uid, f"deadline_s={r.deadline_s} is "
                               "negative (0 means no deadline)")
        top = max(self.ecfg.seq_buckets)
        if p.size > top and not r.allow_truncation:
            raise RequestError(
                r.uid, f"prompt of {p.size} tokens exceeds the largest "
                f"bucket ({top}) and allow_truncation=False")
        try:
            bad = [t for t in r.sampling.stop_tokens
                   if not (isinstance(t, (int, np.integer))
                           and not isinstance(t, bool) and int(t) >= 0)]
        except TypeError:
            raise RequestError(
                r.uid, f"stop_tokens {r.sampling.stop_tokens!r} is not "
                "iterable") from None
        if bad:
            raise RequestError(
                r.uid, f"malformed stop_tokens {r.sampling.stop_tokens!r}: "
                "entries must be non-negative integers")

    def _validate_all(self, requests: List[Request]) -> List[Request]:
        """Partition submissions: malformed requests finish terminally as
        ``rejected`` (empty output, the error attached) and everything
        else is scheduled."""
        live = []
        for r in requests:
            try:
                self.validate_request(r)
            except RequestError as e:
                r.error = e
                r.finish_reason = "rejected"
                r.state = "failed"
                r.output_tokens = np.zeros((0,), np.int32)
                logger.warning("rejected: %s", e)
            else:
                live.append(r)
        return live

    def serve(self, requests: List[Request], *, seed: int = 0,
              handle=None, faults=None) -> List[Request]:
        """Serve a list of requests, grouped by sequence bucket.

        With ``EngineConfig(scheduler=True)`` the transformer families run
        each bucket through the slot-based continuous-batching scheduler
        (per-slot positions, EOS early exit, in-flight slot refill); other
        families — and ``scheduler=False`` — use the legacy batch-at-a-time
        path (equal-size batches, decode to the longest row).

        With ``EngineConfig(paged=True)`` the bucket grouping disappears
        entirely: ONE scheduler (block-paged decode state) serves the whole
        request list, admitting mixed-length requests from different former
        buckets into the same decode batch as pool headroom allows.

        ``handle`` — a :class:`~repro.serving.scheduler.SchedulerHandle`
        whose ``cancel(uid)`` terminates the request at the scheduler's
        next step.  ``faults`` — a
        :class:`~repro.serving.faults.FaultInjector` (deterministic chaos
        harness; re-armed here so repeat serves replay one schedule).
        Both are scheduler-path features; the legacy batch path ignores
        them.  Malformed requests are rejected before any scheduling
        (:meth:`validate_request`) and come back with
        ``finish_reason="rejected"`` and the ``RequestError`` in
        ``Request.error``.
        """
        t0 = time.time()
        self.slot_steps = 0
        self.active_slot_steps = 0
        self.phase_s = {"prefill": 0.0, "decode": 0.0, "idle": 0.0,
                        "refresh": 0.0}
        self.slowest_step = {"s": 0.0, "step": 0, "phase": "",
                             "phase_s": {}}
        self.pages_exhausted_steps = 0
        self.page_pool_stats = {}
        self.prefix_stats = {}
        self.preemptions = 0
        self.refresh_stats = {"refreshes": 0, "deferred_cow": 0,
                              "horizon_extensions": 0}
        self.handle = handle
        self.faults = faults
        if faults is not None:
            faults.reset()
        live = self._validate_all(requests)
        use_sched = ((self.ecfg.scheduler or self.ecfg.paged)
                     and self._supports_scheduler())
        if self.ecfg.paged and use_sched:
            from repro.serving.scheduler import SlotScheduler
            if live:
                seq = max(self._bucket(len(r.prompt)) for r in live)
                SlotScheduler(self, list(live), seq, seed=seed, t0=t0,
                              paged=True).run()
            return requests
        groups: Dict[int, List[Request]] = {}
        for r in live:
            groups.setdefault(self._bucket(len(r.prompt)), []).append(r)
        for seq, grp in groups.items():
            if use_sched:
                from repro.serving.scheduler import SlotScheduler
                SlotScheduler(self, grp, seq, seed=seed, t0=t0).run()
            else:
                for i in range(0, len(grp), self.ecfg.max_batch):
                    self._serve_batch(grp[i: i + self.ecfg.max_batch], seq,
                                      seed, t0=t0)
        return requests

    def _supports_scheduler(self) -> bool:
        """Slot-based continuous batching needs per-slot decode positions —
        a GQA cache contract (per-row seq-axis writes + per-row validity).
        MLA latent caches and the non-transformer families keep the legacy
        batch-at-a-time path (the dense carve-out, same predicate as
        :meth:`_supports_sparse_decode`)."""
        return self._transformer_family() and not self.model.cfg.mla.enabled

    @staticmethod
    def grow_cache(cache, old_len: int, extra: int):
        """Grow KV caches by ``extra`` zero slots: every non-trailing array
        axis whose size equals ``old_len`` is treated as the sequence axis
        (dense KV, MLA latent, and whisper self-attn caches all keep the
        sequence axis before the feature axis).  The trailing axis is never
        grown — it is always a feature/channel dim, and e.g. the RG-LRU
        conv state's channel width can collide with the cache length.  SSM /
        ring-buffer states have no matching axis and pass through.  (The
        paged cache never grows — decode headroom is pre-allocated as tail
        pages; this path serves the legacy contiguous layouts.)"""
        return jax.tree.map(
            lambda x: cache_ops.grow_leaf(x, old_len, extra), cache)

    @staticmethod
    def cache_insert(cache, new, slot: int):
        """Inverse of :meth:`grow_cache`: write one freshly prefilled
        request's KV (batch axis of size 1) into row ``slot`` of the
        running decode cache.

        Transformer-family layout only (the scheduler's contract): prefix
        leaves are ``(B, Hkv, S, hd)`` (batch axis 0), stacked leaves are
        ``(L, B, Hkv, S, hd)`` (batch axis 1).  The new request's shorter
        prefill region is written at sequence offset 0; the slot's decode
        tail keeps whatever the previous occupant wrote — decode validity
        (``slots <= pos[row]``) masks it, so stale tail values never reach
        the softmax and the other rows' numerics are untouched (per-row
        ops share nothing across the batch axis).  The paged twin
        (``paged_cache.insert_prefill``) scatters pages instead of copying
        a whole row."""
        ins = lambda axis: (lambda dst, src:
                            cache_ops.write_slot(dst, src, {axis: slot}))
        return {
            "prefix": [jax.tree.map(ins(0), c, n)
                       for c, n in zip(cache["prefix"], new["prefix"])],
            "stack": jax.tree.map(ins(1), cache["stack"], new["stack"]),
        }

    @staticmethod
    def cache_insert_layer(cache, layer: int, slot: int, k, v, *,
                           offset: int = 0, length: Optional[int] = None):
        """Partial :meth:`cache_insert`: write ONE layer's freshly computed
        K/V (``(Hkv, S, hd)``-shaped after dropping the unit batch axis)
        into row ``slot`` of the running decode cache (``k``/``v`` keep
        their unit batch axis: ``(1, Hkv, S, hd)``; ``offset``/``length``
        trim a packed segment out of the layer's full K/V first).

        This is the incremental-write half of chunked admission: each
        layer's KV lands as soon as its quantum finishes, while decode
        keeps stepping the other slots.  Safe by construction — prefill
        writes stay in ``[0, seq)`` of the admitted slot while an inert
        slot's decode writes land at its frozen tail position, and decode
        validity masks the admitted row until its DecodePlan row is
        spliced.  Stacked transformer layout only (``(L, B, Hkv, S, hd)``);
        prefix layers are refused by ``make_chunk_prefill``.  The paged
        twin is ``paged_cache.insert_prefill_layer`` (same segment slicing,
        pages instead of a row write)."""
        if length is not None:
            # packed run: slice segment [offset, offset+length) out of the
            # packed sequence axis; the segment always lands at the START of
            # its own slot's row (slot-local positions restart at 0)
            k = cache_ops.slice_segment(k, offset, length, axis=2)
            v = cache_ops.slice_segment(v, offset, length, axis=2)
        ck, cv = cache["stack"]
        # k[None]: (1, 1, Hkv, Sseg, hd) — rank-matches the (L, B, Hkv, S,
        # hd) stack leaf; the write lands at [layer, slot, :, 0:Sseg, :]
        ck = cache_ops.write_slot(ck, k[None], {0: layer, 1: slot})
        cv = cache_ops.write_slot(cv, v[None], {0: layer, 1: slot})
        return {"prefix": cache["prefix"], "stack": (ck, cv)}

    def _supports_sparse_decode(self) -> bool:
        cfg = self.model.cfg
        return (cfg.family in ("dense", "vlm", "moe")
                and not cfg.mla.enabled)

    def _sample_batch(self, key: jax.Array, logits: jnp.ndarray,
                      grp: List[Request]) -> np.ndarray:
        """Sample one token per request, honouring each request's own
        SamplingConfig (rows sharing a config are sampled together)."""
        by_cfg: Dict[SamplingConfig, List[int]] = {}
        for i, r in enumerate(grp):
            by_cfg.setdefault(r.sampling, []).append(i)
        toks = np.zeros((len(grp),), np.int32)
        subkeys = jax.random.split(key, len(by_cfg))
        for (scfg, rows), sub in zip(sorted(by_cfg.items(),
                                            key=lambda kv: kv[1][0]),
                                     subkeys):
            t = sample_token(sub, logits[np.asarray(rows)], scfg)
            toks[np.asarray(rows)] = np.asarray(t)
        return toks

    def _pad_prompt(self, r: Request, seq: int, row: np.ndarray) -> int:
        """Left-align one prompt into ``row``; flag + warn on clipping (a
        prompt longer than the largest bucket loses its head silently
        otherwise).  A preempted request re-enters here unchanged — the
        resume re-prefills the ORIGINAL prompt (bitwise the first
        admission); its carried tokens are replayed through decode, not
        prefilled.  Returns the row's valid prompt length."""
        prompt = r.prompt
        if len(prompt) > seq:
            r.truncated = True
            logger.warning(
                "request %s: prompt of %d tokens exceeds the largest "
                "bucket (%d); clipping to the last %d tokens",
                r.uid, len(prompt), seq, seq)
        p = prompt[-seq:]
        row[: len(p)] = p
        return len(p)

    def _record_prefill_stats(self, result, width: Optional[int],
                              seq: int) -> Dict[str, float]:
        """Pattern stats for one prefill + the width-policy observation it
        feeds — shared by the batch path and the scheduler so a new stats
        key or policy branch can never diverge between them."""
        stats = {
            "num_shared": float(result.stats.num_shared),
            "num_dense": float(result.stats.num_dense),
            "num_vs": float(result.stats.num_vs),
            "block_density": float(result.stats.block_density),
            "max_row_pop": float(result.stats.max_row_pop),
            "prefill_width_cap": 0 if width is None else int(width),
        }
        if self.ecfg.width_policy == "auto":
            self._density_obs.setdefault(seq, []).append(
                stats["block_density"])
        elif self.ecfg.width_policy == "count":
            self._pop_obs.setdefault(seq, []).append(
                stats["max_row_pop"])
        return stats

    def _replay_prefill_stats(self, stats: Dict[str, float],
                              seq: int) -> Dict[str, float]:
        """Width-policy observation replay for a prefix-cache hit: the
        hit's hypothetical cold prefill would have produced exactly the
        donor's stats (identical clipped prompt, bucket, and width cap),
        so re-feeding the cached observation keeps the cap evolution —
        and with it every later admission's masks — bitwise-identical to
        the sharing-disabled serve."""
        stats = dict(stats)
        if self.ecfg.width_policy == "auto":
            self._density_obs.setdefault(seq, []).append(
                stats["block_density"])
        elif self.ecfg.width_policy == "count":
            self._pop_obs.setdefault(seq, []).append(
                stats["max_row_pop"])
        return stats

    @staticmethod
    def _decode_rate(n_tokens: int, decode_s: float) -> float:
        """Per-request decode tokens/s: n-1 decode steps produced tokens
        1..n-1 (token 0 comes from the prefill logits)."""
        return ((n_tokens - 1) / decode_s
                if n_tokens > 1 and decode_s > 0 else 0.0)

    @staticmethod
    def _plan_stats(plan, cache_len: int) -> Dict[str, float]:
        """Modeled sparse-decode traffic counters for a built DecodePlan."""
        total, streamed = dplan.plan_block_counts(plan)
        return {
            "decode_traffic_fraction": dplan.plan_traffic_fraction(plan),
            "decode_blocks_total": float(total),
            "decode_blocks_computed": float(streamed),
            "decode_blocks_skipped": float(total - streamed),
            "decode_cache_len": float(cache_len),
        }

    def _serve_batch(self, grp: List[Request], seq: int, seed: int,
                     t0: Optional[float] = None):
        """Prefill the padded batch, then decode autoregressively
        (batch-at-a-time: the batch advances in lockstep; a row that hits a
        stop token or its own ``max_new_tokens`` goes inert and the batch
        exits once every row is done).

        Prompts are left-aligned / right-padded; for the transformer
        families, per-request prompt lengths are threaded (a) into prefill,
        whose last-logits are gathered at each row's ``prompt_len - 1``
        (the first sampled token never conditions on right-pad), and (b)
        into every GQA decode step as a slot-validity mask, so pad K/V
        entries are never attended (remaining simplifications: MLA /
        non-transformer caches still attend pads, and prefill attention
        itself runs over the padded batch)."""
        t0 = time.time() if t0 is None else t0
        b = len(grp)
        toks = np.zeros((b, seq), np.int32)
        plens_l = [self._pad_prompt(r, seq, toks[i])
                   for i, r in enumerate(grp)]
        plens = jnp.asarray(plens_l, jnp.int32)

        width = self._width_cap(seq)
        tp = time.time()
        for r in grp:
            r.queue_s = max(tp - (t0 + r.arrival_s), 0.0)
        prefill = self._prefill_fn(b, seq, width)
        result = prefill(self.params, jnp.asarray(toks), plens)
        jax.block_until_ready(result.last_logits)
        prefill_s = time.time() - tp

        stats = self._record_prefill_stats(result, width, seq)

        max_new = max(r.max_new_tokens for r in grp)
        key = jax.random.PRNGKey(seed)
        extra = max(max_new, self.ecfg.decode_extra)
        # decode headroom stays a block multiple so the sparse-decode block
        # tables tile the grown cache exactly
        blk = max(self.sp.cfg.block_size, 1)
        extra = ((extra + blk - 1) // blk) * blk
        cache = self.grow_cache(result.cache, seq, extra)

        # decode-phase pattern sharing (beyond paper): compile the prefill
        # pattern dictionary into block tables ONCE for the whole batch —
        # every decode step reuses them (see repro.serving.decode_plan)
        use_sparse = (self.ecfg.decode_sparse
                      and self.ecfg.method == "share"
                      and result.sp_state is not None
                      and self._supports_sparse_decode())
        plan = None
        if use_sparse:
            # under a heads-sharded mesh each shard's tables are built
            # locally (kv_head_range) and laid out sharded — the execution
            # side is resolved by the decode step itself
            plan = dplan.build_decode_plan_auto(
                self.sp, result.sp_state, self.model.cfg,
                prefill_len=seq, cache_len=seq + extra)
            stats.update(self._plan_stats(plan, seq + extra))

        decode = self._decode_fn(b, seq, seq + extra, use_sparse)
        logits = result.last_logits
        outs = [[] for _ in range(b)]
        done = [False] * b
        t1 = time.time()
        finish = [t1] * b
        for i, r in enumerate(grp):
            if r.max_new_tokens <= 0:   # prefill-only: no token is emitted
                done[i], r.finish_reason = True, "length"
        for t in range(max_new):
            key, sub = jax.random.split(key)
            tok = self._sample_batch(sub, logits, grp)
            now = time.time()
            if t == 0:
                # prefill-only rows (max_new_tokens <= 0) emit no token, so
                # they record no TTFT — matching the scheduler path
                for r in grp:
                    if r.max_new_tokens > 0:
                        r.ttft_s = max(now - (t0 + r.arrival_s), 0.0)
            for i, r in enumerate(grp):
                if done[i]:
                    continue                 # inert row: sampled, discarded
                outs[i].append(int(tok[i]))
                if r.sampling.is_stop(int(tok[i])):
                    done[i], r.finish_reason = True, "stop"
                elif len(outs[i]) >= r.max_new_tokens:
                    done[i], r.finish_reason = True, "length"
                if done[i]:
                    finish[i] = now
            if all(done):
                break
            # occupancy: a lockstep decode step burns max_batch slot-steps
            # of capacity however few rows still need tokens
            self.slot_steps += self.ecfg.max_batch
            self.active_slot_steps += b - sum(done)
            tok_j = jnp.asarray(tok)[:, None]
            if use_sparse:
                logits, cache = decode(self.params, tok_j, cache,
                                       jnp.int32(seq + t), plens, plan)
            else:
                logits, cache = decode(self.params, tok_j, cache,
                                       jnp.int32(seq + t), plens)

        for i, r in enumerate(grp):
            r.output_tokens = np.asarray(outs[i], np.int32)
            r.prefill_s = prefill_s
            r.decode_s = max(finish[i] - t1, 0.0)
            r.decode_tokens_per_s = self._decode_rate(len(outs[i]),
                                                      r.decode_s)
            r.pattern_stats = stats
            r.state = "done"        # the batch path has no cancellation /
                                    # quarantine reaper; rows end DONE
