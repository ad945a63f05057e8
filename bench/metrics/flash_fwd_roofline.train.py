"""Share of its roofline that ``kernels/flash_fwd.py`` reached in training:
the attention forward work of each step's packed documents (counted once per
kernel call, forward and rematerialised alike) at the chip's peak, over the
kernel's device time in the trace."""

from bench.counts import attn_fwd, roofline_share
from bench.harness import log


def read(res, dims, peaks):
    k = res.reduced.kernels.get("flash_fwd") if res.reduced else None
    steps = res.counters.get("step_rows")
    if not k or not k["calls"] or not steps:
        return None
    per_step = k["calls"] / len(steps)
    flops = nbytes = 0
    for rows in steps:
        for r in rows:
            f, b = attn_fwd(dims, r)
            flops += f * per_step
            nbytes += b * per_step
    share, bound = roofline_share(flops, nbytes, k["seconds"], peaks)
    log(f"flash_fwd_roofline.train: {bound}-bound, {k['calls']:.0f} calls, "
        f"{per_step:g} a step")
    return share
