"""The held-expert layer's share of the chip's bf16 peak, %: 2 x 3 x
hidden_size x moe_intermediate_size operations per row routed to a held
expert (the requests' ``expert_rows`` counters, prefill and decode, summed
over MoE layers) over the device time of the grouped matmul (the trace's
``<program>:ragged-dot-*`` operations: ``jax.lax.ragged_dot``'s kernel and
its group metadata, in every program) times the peak.  Nothing to read
where no request counts rows or the trace holds no grouped matmul."""


def read(run):
    if run.trace is None:
        return None
    rows = sum(int(r.expert_rows.sum()) for r in run.done
               if getattr(r, "expert_rows", None) is not None)
    busy = sum(s for op, s in run.trace.op_s.items()
               if op.split(":", 1)[-1].startswith("ragged-dot"))
    if not rows or not busy:
        return None
    flops = 6.0 * run.sizes["hidden_size"] \
        * run.sizes["moe_intermediate_size"] * rows
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])
