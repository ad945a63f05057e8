"""Share of its roofline that ``kernels/flash_bwd.py`` (the dK/dV and the dQ
kernel together) reached in training: the four backward matmuls of each
step's packed documents at the chip's peak, over the kernels' device time."""

from bench.counts import attn_bwd, roofline_share
from bench.harness import log


def read(res, dims, peaks):
    k = res.reduced.kernels.get("flash_bwd") if res.reduced else None
    steps = res.counters.get("step_rows")
    if not k or not k["calls"] or not steps:
        return None
    # one dK/dV and one dQ call per layer make one backward
    per_step = k["calls"] / 2 / len(steps)
    flops = nbytes = 0
    for rows in steps:
        for r in rows:
            f, b = attn_bwd(dims, r)
            flops += f * per_step
            nbytes += b * per_step
    share, bound = roofline_share(flops, nbytes, k["seconds"], peaks)
    log(f"flash_bwd_roofline.train: {bound}-bound, {k['calls']:.0f} calls, "
        f"{per_step:g} backwards a step")
    return share
