"""Prefill attention's share of the chip's bf16 peak, %: the family's
``attention_flops`` over the causal blocks each finished request's prompt
kept (``work.kept_blocks`` at the request's block density, the count
``step_mfu.throughput`` uses; a density of 0 or none reported is a dense
run, so every causal block counts) over the device time of the ``attn``
programs (the chunked-admission attention quanta) times the peak.
Nothing to read where the trace holds no ``attn`` program."""
from bench import work


def read(run):
    if run.trace is None or not run.trace.module_s.get("attn"):
        return None
    blocks = 0.0
    for r in run.done:
        density = (r.pattern_stats or {}).get("block_density") or 1.0
        blocks += work.kept_blocks(density, len(r.prompt), run.block)
    if not blocks:
        return None
    flops = run.family.attention_flops(blocks, run.block, run.sizes)
    return 100.0 * flops \
        / (run.trace.module_s["attn"] * run.peaks["bf16_flops_per_s"])
