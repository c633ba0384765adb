# Flight-recorder observability plane (DESIGN.md §13).
#
# Three pieces.  They run on the host, except the named scopes the
# sweep program opens for its phases
# (``repro.obs.trace.PROGRAM_PHASES``): metadata on its compiled
# instructions, so golden parity holds bit-for-bit with recording on
# or off:
#
# * ``repro.obs.trace``   — structured spans around the engine's
#   dispatch/transfer/host-slice phases, emitted as a JSONL event log
#   plus a Chrome-trace (``trace_event``) export viewable in Perfetto;
#   JAX's compile events as counters; the program registry whose
#   op-to-phase map attributes device time to the tick's phases;
# * ``repro.obs.windows`` — the shared warmup/stable/cooldown windowing
#   contract (EWMA-slope + variance plateau) every E-series runner uses
#   so artifact cells carry stable-only statistics next to whole-run
#   numbers;
# * ``repro.obs.report``  — the ``repro-report`` CLI
#   (``python -m repro.obs.report``): per-phase time breakdown,
#   compile-vs-execute ratios, windowed-vs-raw metric deltas, and a
#   ``--check`` mode CI runs against every trace/artifact pair.
from repro.obs import trace, windows  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    RECORDER,
    Recorder,
    configure,
    instant,
    span,
)
from repro.obs.windows import (  # noqa: F401
    Window,
    cell_block,
    detect,
    q_mean_series,
)
