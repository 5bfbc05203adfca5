"""Driver state for one chunked (optionally packed) admission.

:class:`ChunkedPrefillRun` owns everything the scheduler needs to advance an
in-flight admission one quantum at a time: the padded (packed) token row,
per-segment positions/prompt lengths, the pattern-sharing state threaded
across layers, and a small phase machine over the quantum sequence

    begin → [layer_begin → chunk × C → layer_end] × L → finish

(the jitted programs come from :meth:`ServingEngine._chunk_fns`; the
decomposition itself lives in ``repro.models.chunked_prefill``).  Each
:meth:`step` executes exactly ONE quantum and blocks on its outputs, so the
scheduler's interleave loop — one quantum, then one decode step — bounds how
long any admission can stall the occupied slots.

Two events surface to the caller:

``"kv"``   a layer's K/V just became final (``kv_layer``, ``kv``) — the
           scheduler writes it into the admitted slot(s) immediately
           (:meth:`ServingEngine.cache_insert_layer`), per packed segment,
           while decode keeps running between quanta.
``"done"`` the final quantum ran: ``logits`` holds each segment's
           last-token logits (P, V), ``sp_state`` the post-prefill pattern
           dictionary, ``attn_stats`` the layer-reduced pattern stats —
           everything :class:`~repro.serving.scheduler.SlotScheduler` needs
           to splice DecodePlan rows and sample first tokens.

Packing (P > 1) concatenates same-bucket prompts into one ``(1, P·seq)``
row: positions restart per segment, a block-diagonal segment mask isolates
attention (``core.patterns.segment_block_mask``), and each segment's K/V
slice lands in its own slot.  The pattern dictionary is shared across the
packed row — the documented trade-off that keeps packing opt-in.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import AttnStats


class ChunkedPrefillRun:
    """One in-flight chunked admission (a packed group of 1+ requests)."""

    def __init__(self, eng, requests: List, slot_ids: List[int], seq: int,
                 chunk_tokens: int, width: Optional[int]):
        self.eng = eng
        self.requests = requests
        self.slot_ids = slot_ids
        self.seq = seq
        self.width = width
        self.P = len(requests)
        total = self.P * seq
        self.total = total

        sp = eng.sp
        bs = min(sp.cfg.block_size if sp.cfg.enabled else 128, total)
        if total % bs:
            raise ValueError(f"bucket {seq} (packed total {total}) does not "
                             f"tile block size {bs}")
        self.bs = bs
        self.nb = total // bs
        # packed runs carry the per-segment isolation mask; a solo run is
        # exactly the one-shot mask geometry (seg_blocks=None)
        self.seg_blocks = seq // bs if self.P > 1 else None
        cnb = max(chunk_tokens // bs, 1)
        self.chunks: List[Tuple[int, int]] = [
            (o, min(cnb, self.nb - o)) for o in range(0, self.nb, cnb)]

        toks = np.zeros((1, total), np.int32)
        self.plens = [eng._pad_prompt(r, seq, toks[0, j * seq:(j + 1) * seq])
                      for j, r in enumerate(requests)]
        self.tokens = jnp.asarray(toks)
        # positions restart per segment — each packed prompt ropes as if it
        # were alone at the start of its own slot
        self.positions = jnp.asarray(
            np.tile(np.arange(seq, dtype=np.int32), self.P)[None])

        applicable = sp.cfg.enabled and sp.applicable(total)
        self.sp_state = sp.init_state(1, total) if applicable else None
        self.cluster_arr = sp.layer_cluster_ids() if applicable else None
        self.fns = eng._chunk_fns(total, width, self.seg_blocks)
        self.num_layers = eng.model.cfg.num_layers

        self.x = None
        self.layer = 0
        self._phase = "begin"
        self.chunk_i = 0
        self._q = self._k = self._v = None
        self._masks = self._decision = self._gate = self._perm = None
        self._outs: List = []
        self._ats: List = []
        self._layer_stats: List = []
        self.kv = None              # (k, v) of the layer just finalized
        self.kv_layer = -1
        self.logits = None          # (P, V) after the finish quantum
        self.attn_stats: Optional[AttnStats] = None
        self.quanta_done = 0
        self.quanta_total = 2 + self.num_layers * (2 + len(self.chunks))

    @property
    def done(self) -> bool:
        return self._phase == "done"

    @property
    def quantum(self) -> str:
        """The next quantum's kind, named after the program it runs:
        ``begin``, ``layer_begin``, ``attn`` (chunk ``chunk_i`` of layer
        ``layer``), ``layer_end`` or ``finish``."""
        return "attn" if self._phase == "chunk" else self._phase

    def abort(self) -> None:
        """Abandon the run between quanta (cancellation / deadline /
        quarantine): drop every device reference so the admission's working
        set is released immediately.  Terminal — a later :meth:`step`
        raises; the scheduler releases the granted pages and slots itself,
        and any K/V the run already inserted is harmless (the slots were
        never occupied, so validity masks keep the partial rows dark)."""
        self.x = None
        self._q = self._k = self._v = None
        self._masks = self._decision = self._gate = self._perm = None
        self._outs, self._ats, self._layer_stats = [], [], []
        self.kv = None
        self.sp_state = None
        self.logits = None
        self._phase = "done"

    def step(self) -> Optional[str]:
        """Run ONE quantum to completion (device-synchronous). Returns
        ``"kv"`` when a layer's K/V is ready to insert, ``"done"`` after the
        final quantum, else ``None``."""
        eng = self.eng
        ev = None
        if self._phase == "begin":
            self.x = self.fns["begin"](eng.params, self.tokens)
            jax.block_until_ready(self.x)
            self._phase = "layer_begin"

        elif self._phase == "layer_begin":
            li = jnp.int32(self.layer)
            (self._q, self._k, self._v, self._masks, self._decision,
             self._gate, self._perm) = self.fns["layer_begin"](
                 eng.params, li, self.x, self.positions, self.sp_state,
                 self.cluster_arr)
            jax.block_until_ready(self._q)
            self._outs, self._ats = [], []
            self.chunk_i = 0
            self._phase = "chunk"

        elif self._phase == "chunk":
            cs, cb = self.chunks[self.chunk_i]
            out, at = self.fns["attn"](
                self._q, self._k, self._v, self._masks, self._gate,
                self._perm, chunk_start=cs, chunk_blocks=cb)
            jax.block_until_ready(out)
            self._outs.append(out)
            if at is not None:
                self._ats.append(at)
            self.chunk_i += 1
            if self.chunk_i == len(self.chunks):
                self._phase = "layer_end"

        elif self._phase == "layer_end":
            li = jnp.int32(self.layer)
            ats = self._ats if self._ats else None
            self.x, self.kv, self.sp_state, stats = self.fns["layer_end"](
                eng.params, li, self.x, self._outs, self._k, self._v, ats,
                self._masks, self._decision, self.sp_state, self.cluster_arr)
            jax.block_until_ready(self.x)
            self._layer_stats.append(stats)
            self.kv_layer = self.layer
            self._q = self._k = self._v = None
            self._masks = self._decision = self._gate = self._perm = None
            self._outs, self._ats = [], []
            self.layer += 1
            self._phase = ("finish" if self.layer == self.num_layers
                           else "layer_begin")
            ev = "kv"

        elif self._phase == "finish":
            rows = np.asarray(
                [j * self.seq + max(min(p, self.seq), 1) - 1
                 for j, p in enumerate(self.plens)], np.int32)
            bidx = np.zeros((self.P,), np.int32)
            self.logits = self.fns["finish"](
                eng.params, self.x, jnp.asarray(bidx), jnp.asarray(rows))
            jax.block_until_ready(self.logits)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *self._layer_stats)
            self.attn_stats = AttnStats.reduce_layers(stacked)
            self.x = None
            self._phase = "done"
            ev = "done"

        else:
            raise RuntimeError("step() on a completed ChunkedPrefillRun")
        self.quanta_done += 1
        return ev
