"""Share of its roofline that ``kernels/flash_fwd.py`` reached inside the
engine's packed prefill: causal attention within each prompt of every packed
row in the window (padding is no work), at the chip's peak, over the
kernel's device time."""

from bench.counts import attn_fwd, prompt_lengths, roofline_share
from bench.harness import log


def read(res, dims, peaks):
    k = res.reduced.kernels.get("flash_fwd") if res.reduced else None
    calls = res.counters.get("prefill_segments")
    if not k or not k["calls"] or not calls:
        return None
    per_call = k["calls"] / len(calls)
    flops = nbytes = 0
    for seg in calls:
        f, b = attn_fwd(dims, prompt_lengths(seg))
        flops += f * per_call
        nbytes += b * per_call
    share, bound = roofline_share(flops, nbytes, k["seconds"], peaks)
    log(f"prefill_attn_roofline.serve: {bound}-bound, {k['calls']:.0f} "
        f"calls, {per_call:g} a prefill")
    return share
