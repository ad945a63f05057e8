"""Share of its roofline that the paged decode kernel (``kernels/decode.py``)
reached while serving: for each decode step in the window, one query token
per live row against that row's keys (its cached tokens and the new one), at
the chip's peak, over the kernel's device time."""

from bench.counts import paged_decode, roofline_share
from bench.harness import log


def read(res, dims, peaks):
    k = res.reduced.kernels.get("paged_decode") if res.reduced else None
    calls = res.counters.get("decode_kv_len")
    if not k or not k["calls"] or not calls:
        return None
    per_call = k["calls"] / len(calls)
    flops = nbytes = 0
    for kv in calls:
        f, b = paged_decode(dims, [int(n) + 1 for n in kv if n > 0])
        flops += f * per_call
        nbytes += b * per_call
    share, bound = roofline_share(flops, nbytes, k["seconds"], peaks)
    log(f"paged_decode_roofline.serve: {bound}-bound, {k['calls']:.0f} "
        f"calls, {per_call:g} a step")
    return share
