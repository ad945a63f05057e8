"""Model FLOP utilization of the engine's jitted paged decode step: model
FLOPs of the window's decode steps (each live row's token through the model
and its attention over its keys) over those steps' device time and the
chip's peak."""

from bench.counts import decode_flops

MODULE = "jit_decode_fn"


def read(res, dims, peaks):
    m = res.reduced.modules.get(MODULE) if res.reduced else None
    calls = res.counters.get("decode_kv_len")
    if not m or not m["seconds"] or not calls:
        return None
    flops = sum(decode_flops(dims, [int(n) + 1 for n in kv if n > 0])
                for kv in calls)
    return 100.0 * flops / m["seconds"] / peaks["bf16_flops_per_s"]
