"""Model FLOP utilization of the engine's jitted packed prefill step: model
FLOPs of the window's prompts (every layer on every prompt token, causal
attention within each prompt, the LM head on each prompt's last position)
over those steps' device time and the chip's peak."""

from bench.counts import prefill_flops, prompt_lengths

MODULE = "jit_prefill_fn"


def read(res, dims, peaks):
    m = res.reduced.modules.get(MODULE) if res.reduced else None
    calls = res.counters.get("prefill_segments")
    if not m or not m["seconds"] or not calls:
        return None
    flops = sum(prefill_flops(dims, prompt_lengths(seg)) for seg in calls)
    return 100.0 * flops / m["seconds"] / peaks["bf16_flops_per_s"]
