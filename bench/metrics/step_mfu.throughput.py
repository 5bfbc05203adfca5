"""Whole-serve share of the chip's bf16 peak, %: the operations of the
work done (every layer's projections per real prompt and output token, the
output head per output token, attention over the blocks kept; padding not
counted) over the traced window times the peak."""
from bench import work


def read(run):
    if run.trace is None or not run.done:
        return None
    flops = work.served_flops(run.done, run.block, run.family, run.sizes)
    return 100.0 * flops \
        / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
