"""Seconds of set-up spent in backend compiles, each a compile or a
load from the persistent compile cache: the union of JAX's
backend-compile and cache-retrieval events up to the reset that opens
the traced window, from the program's compile counters
(``repro.obs.trace.Recorder.compile_at_reset``)."""


def read(ctx):
    from repro.obs import trace as obs

    at = getattr(obs.RECORDER, "compile_at_reset", None)
    if not at or "compile_load_s" not in at:
        return None
    return at["compile_load_s"]
