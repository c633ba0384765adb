# The fourth registry (after policies, workloads, controllers): typed
# fault events compiled host-side into time-indexed schedules that ride
# the engine's scan xs — jittable, sweep-vmappable, and provably
# zero-cost when no event fires.  See base.py for the schema and
# events.py for the built-in vocabulary.
from repro.core.faults import events  # noqa: F401  (registration)
from repro.core.faults.base import (  # noqa: F401
    AVAIL_FULL,
    DETECT_TIMEOUT_MS,
    STORM_LANES,
    CompiledFaults,
    FaultEvent,
    FaultSpec,
    FaultTickInfo,
    FaultXs,
    Schedule,
    apply_traffic,
    available,
    compile_faults,
    counters,
    detect_available,
    detect_ticks,
    feasible_by_epoch,
    get,
    get_class,
    make_xs,
    normalize,
    parse_fault,
    register,
    tick_info,
    unregister,
    validate_events,
)
from repro.core.faults.events import storm_from_pool  # noqa: F401
from repro.core.faults.programs import (  # noqa: F401
    CascadeEvent,
    detection_tick,
    overlap,
    resolve,
    rolling,
    sequence,
)
