"""Model FLOPs of the real tokens the traced window processed (prompts
in prefill, one token per active sequence in decode, attention at each
token's context) over the window's length times the chip's bf16 peak."""
import timing


def read(run):
    if not run.trace or not run.peak:
        return None
    m = run.model
    flops = 0
    for s in timing.window_steps(run):
        flops += sum(m.prefill_flops(run.requests[rid].prompt_len)
                     for rid in s.admitted)
        flops += m.decode_flops(s.contexts)
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
