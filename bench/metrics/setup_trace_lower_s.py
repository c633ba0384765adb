"""Seconds of set-up spent tracing and lowering programs: the union of
JAX's trace and lower events up to the reset that opens the traced
window, from the program's compile counters
(``repro.obs.trace.Recorder.compile_at_reset``)."""


def read(ctx):
    from repro.obs import trace as obs

    at = getattr(obs.RECORDER, "compile_at_reset", None)
    if not at or "trace_lower_s" not in at:
        return None
    return at["trace_lower_s"]
