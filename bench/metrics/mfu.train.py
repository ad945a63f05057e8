"""Model FLOP utilization of the training step: the model FLOPs of the
window's steps (6 per weight per token, attention forward and backward from
the segment layout, no recomputation) over the window and the chip's peak."""

from bench.counts import train_step_flops


def read(res, dims, peaks):
    steps = res.counters.get("step_rows")
    if not steps or not res.window_s:
        return None
    flops = sum(train_step_flops(dims, rows) for rows in steps)
    return 100.0 * flops / res.window_s / peaks["bf16_flops_per_s"]
