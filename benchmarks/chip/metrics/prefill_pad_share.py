"""Share of the tokens pushed through prefill in the window that are
bucket padding: EngineStats.prefill_tokens against the real prompt
tokens of the requests admitted in the window."""
import timing


def read(run):
    if not run.prefill_tokens:
        return None
    real = sum(run.requests[rid].prompt_len
               for s in timing.window_steps(run) for rid in s.admitted)
    return 100.0 * (run.prefill_tokens - real) / run.prefill_tokens
