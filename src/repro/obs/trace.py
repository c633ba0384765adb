"""Structured run traces: spans, compile counters, the program's phases.

The flight recorder wraps the engine's host-side orchestration phases —
warmup, dispatch, device transfer, host-side slicing — in :func:`span`
context managers.  Each completed span becomes one event dict; events
use the Chrome ``trace_event`` keys directly (``name``, ``cat``, ``ph``,
``ts``, ``dur``, ``pid``, ``tid``, ``args``) so the JSONL log is
simultaneously the structured schema *and*, wrapped in
``{"traceEvents": [...]}``, a file Perfetto / ``chrome://tracing`` opens
as-is.  Timestamps are microseconds on the recorder's monotonic clock;
the wall-clock epoch rides a metadata event so traces can be joined
with artifact ``meta`` timestamps.

Two more records reach below the host spans (DESIGN.md §13):

* **Compile pipeline.**  A ``jax.monitoring`` listener
  (:func:`listen_compile`, registered once by the engine, which loads
  JAX; importing this module does not) records JAX's tracing,
  lowering, backend-compile and compile-cache events, while the global
  recorder is enabled, as ``compile`` spans and as process-lifetime
  counters (:class:`CompileCounters`: seconds as a union of intervals,
  since inner traces nest in outer ones, plus event counts and cache
  hits and misses).  ``configure(fresh=True)`` keeps
  the totals as they were at that reset (``Recorder.compile_at_reset``).
* **Device phases.**  The sweep program opens a ``jax.named_scope`` per
  phase of its tick (:data:`PROGRAM_PHASES`); a scope is metadata on the compiled program's instructions and
  changes no result.  ``run_sweep`` registers each sweep executable it
  compiles (:func:`register_program`); :func:`phase_map` reads the
  registered programs' HLO text, on first ask, into an instruction ->
  (phase, sub-scope) map that a profiler trace's ops can be looked up
  in.

Nothing here runs per tick on the host: engine results are bit-for-bit
identical with the recorder enabled or disabled (tested), and the
overhead is a few dict appends per sweep and per compile event.

Write-through sink: when a JSONL path is configured (the benchmark
:class:`benchmarks.common.Artifact` pairs one with every JSON artifact),
each completed event is appended immediately, so a CI timeout that
kills the process mid-run still leaves a valid prefix of whole lines.
``REPRO_OBS=0`` disables recording entirely; ``REPRO_OBS_PROFILE=1``
additionally wraps every span in a ``jax.profiler.TraceAnnotation`` so
spans line up with XLA traces in a profiler capture.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from collections import Counter
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

SCHEMA_VERSION = 1

# the event keys --check requires; everything else is optional
REQUIRED_KEYS = ("name", "cat", "ph", "ts", "pid", "tid")
PHASES = ("X", "i", "M")  # complete span, instant, metadata


# jax.monitoring duration events -> the compile counter they add to
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# jax.monitoring events that are counted
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileCounters:
    """Totals of the compile pipeline: per kind of event (``trace``,
    ``lower``, ``compile``, ``cache_retrieval``) its event count, the
    persistent-cache hits and misses, and the seconds of two groups of
    kinds.  Seconds are a union of intervals, since JAX reports a
    nested trace inside the outer one: ``trace_lower`` covers tracing
    and lowering, ``compile_load`` backend compiles and the cache
    retrievals they wrap."""

    GROUPS = {
        "trace_lower": ("trace", "lower"),
        "compile_load": ("compile", "cache_retrieval"),
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._merged: Dict[str, List[Tuple[float, float]]] = {
            g: [] for g in self.GROUPS
        }
        self.counts: Dict[str, int] = dict.fromkeys(
            tuple(COMPILE_EVENTS.values()) + tuple(CACHE_EVENTS.values()),
            0,
        )

    def add(self, kind: str, start: float, end: float) -> None:
        """One event of ``kind`` over [start, end] (seconds)."""
        with self._lock:
            self.counts[kind] += 1
            for g, kinds in self.GROUPS.items():
                if kind in kinds:
                    _merge_into(self._merged[g], start, end)

    def count(self, kind: str) -> None:
        with self._lock:
            self.counts[kind] += 1

    def totals(self) -> dict:
        """``<group>_s`` seconds for every group, and every count."""
        with self._lock:
            out = {
                f"{g}_s": sum(e - s for s, e in iv)
                for g, iv in self._merged.items()
            }
            out.update(self.counts)
        return out


def _merge_into(ivs: List[Tuple[float, float]], s: float, e: float):
    """Merge [s, e] into ``ivs``, sorted disjoint intervals, in place.
    Events arrive at their end, so the work is at the tail."""
    j = len(ivs)
    while j > 0 and ivs[j - 1][0] > e:
        j -= 1
    k = j
    while k > 0 and ivs[k - 1][1] >= s:
        k -= 1
    if k < j:
        s, e = min(s, ivs[k][0]), max(e, ivs[j - 1][1])
    ivs[k:j] = [(s, e)]


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


class Recorder:
    """Append-only span recorder with an optional JSONL write-through
    sink.  One process-global instance (:data:`RECORDER`) serves the
    engine and the benchmark harness; tests build private ones.

    JAX's compile events, handed to :meth:`_on_duration` and
    :meth:`_on_event` (the global recorder's by :func:`listen_compile`),
    become ``compile`` spans and add to :attr:`compile` (process-lifetime
    counters) while the recorder is enabled; :attr:`compile_at_reset`
    holds their totals as they were at the last
    ``configure(fresh=True)``."""

    def __init__(self, enabled: Optional[bool] = None):
        self._lock = threading.Lock()
        self.events: List[dict] = []
        self.path: Optional[Path] = None
        self.enabled = (
            _env_flag("REPRO_OBS", True) if enabled is None else enabled
        )
        self.profile = _env_flag("REPRO_OBS_PROFILE", False)
        self._epoch_perf = time.perf_counter()
        self._epoch_wall = time.time()
        self.compile = CompileCounters()
        self.compile_at_reset: Optional[dict] = None

    # -- configuration ----------------------------------------------------
    def configure(
        self,
        path=None,
        enabled: Optional[bool] = None,
        profile: Optional[bool] = None,
        fresh: bool = False,
    ) -> None:
        """Point the recorder at a JSONL sink (and optionally reset).

        ``fresh=True`` clears buffered events and truncates the sink —
        the per-artifact idiom: one trace file per benchmark artifact —
        and keeps the compile counters' totals as they are now in
        :attr:`compile_at_reset`.
        """
        with self._lock:
            if enabled is not None:
                self.enabled = enabled
            if profile is not None:
                self.profile = profile
            if fresh:
                self.events.clear()
                self._epoch_perf = time.perf_counter()
                self._epoch_wall = time.time()
                self.compile_at_reset = self.compile.totals()
            if path is not None:
                self.path = Path(path)
                self.path.parent.mkdir(parents=True, exist_ok=True)
                if fresh or not self.path.exists():
                    self.path.write_text("")
        if self.enabled:
            self._record(self._meta_event())

    def _meta_event(self) -> dict:
        return {
            "v": SCHEMA_VERSION,
            "name": "recorder",
            "cat": "meta",
            "ph": "M",
            "ts": 0.0,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFF,
            "args": {
                "epoch_unix": round(self._epoch_wall, 6),
                "schema": SCHEMA_VERSION,
            },
        }

    # -- event emission ---------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch_perf) * 1e6

    def _record(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)
            if self.path is not None:
                with self.path.open("a") as f:
                    f.write(json.dumps(ev) + "\n")

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase", **args) -> Iterator[dict]:
        """Record one complete (``ph="X"``) event around a code block.

        ``cat`` buckets the phase taxonomy (``warmup`` / ``execute`` /
        ``host`` / ``bench`` — DESIGN.md §13); extra keyword args land
        in the event's ``args`` and must be JSON-serializable.  Yields
        the args dict — mutate it inside the block to attach facts only
        known afterwards (e.g. ``compiled``).  A span that exits via an
        exception is still recorded, with the exception type in
        ``args.error``.
        """
        args = dict(args)
        if not self.enabled:
            yield args
            return
        ctx = contextlib.nullcontext()
        if self.profile:
            try:
                import jax

                ctx = jax.profiler.TraceAnnotation(name)
            except Exception:  # profiler unavailable: spans still record
                ctx = contextlib.nullcontext()
        t0 = self._now_us()
        err = None
        try:
            with ctx:
                yield args
        except BaseException as e:
            err = type(e).__name__
            raise
        finally:
            t1 = self._now_us()
            if err is not None:
                args["error"] = err
            self._record(
                {
                    "v": SCHEMA_VERSION,
                    "name": name,
                    "cat": cat,
                    "ph": "X",
                    "ts": round(t0, 3),
                    "dur": round(t1 - t0, 3),
                    "pid": os.getpid(),
                    "tid": threading.get_ident() & 0xFFFF,
                    "args": args,
                }
            )

    def instant(self, name: str, cat: str = "mark", **args) -> None:
        """Record one instantaneous (``ph="i"``) event."""
        if not self.enabled:
            return
        self._record(
            {
                "v": SCHEMA_VERSION,
                "name": name,
                "cat": cat,
                "ph": "i",
                "ts": round(self._now_us(), 3),
                "s": "p",
                "pid": os.getpid(),
                "tid": threading.get_ident() & 0xFFFF,
                "args": args,
            }
        )

    # -- compile pipeline -------------------------------------------------
    def _on_duration(self, event: str, duration: float, **kw) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is None or not self.enabled:
            return
        end = time.perf_counter()
        start = end - float(duration)
        self.compile.add(kind, start, end)
        t0 = max((start - self._epoch_perf) * 1e6, 0.0)
        t1 = (end - self._epoch_perf) * 1e6
        args = {k: v for k, v in kw.items() if isinstance(v, (str, int))}
        self._record(
            {
                "v": SCHEMA_VERSION,
                "name": f"compile/{kind}",
                "cat": "compile",
                "ph": "X",
                "ts": round(t0, 3),
                "dur": round(max(t1 - t0, 0.0), 3),
                "pid": os.getpid(),
                "tid": threading.get_ident() & 0xFFFF,
                "args": args,
            }
        )

    def _on_event(self, event: str, **kw) -> None:
        kind = CACHE_EVENTS.get(event)
        if kind is not None and self.enabled:
            self.compile.count(kind)

    # -- export -----------------------------------------------------------
    def write_chrome(self, path) -> Path:
        """Write the buffered events as one Chrome-trace JSON document
        (``{"traceEvents": [...]}``) Perfetto opens directly."""
        path = Path(path)
        with self._lock:
            doc = {
                "traceEvents": list(self.events),
                "displayTimeUnit": "ms",
            }
        path.write_text(json.dumps(doc))
        return path


# The process-global recorder the engine and harness share.
RECORDER = Recorder()
_listening = False


def listen_compile() -> None:
    """Hand JAX's compile events to the global recorder, once per
    process.  The engine calls this on import: a process that imports
    only ``repro.obs`` (a parent that launches chip workers, the report
    CLI) never loads JAX."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(
        RECORDER._on_duration
    )
    jax.monitoring.register_event_listener(RECORDER._on_event)
    _listening = True


def configure(**kw) -> None:
    RECORDER.configure(**kw)


def span(name: str, cat: str = "phase", **args):
    return RECORDER.span(name, cat=cat, **args)


def instant(name: str, cat: str = "mark", **args) -> None:
    RECORDER.instant(name, cat=cat, **args)


# ---------------------------------------------------------------------------
# Device phases: named scopes and the op-to-phase map
# ---------------------------------------------------------------------------

# The phases of the sweep program.  An op's phase is the outermost of
# these in its name stack, so a middleware hook the slow loop calls
# counts as control.
PROGRAM_PHASES = (
    "tick/faults",
    "tick/middleware",
    "tick/route",
    "tick/queues",
    "tick/control",
    "tick/summary",
    "sweep/feasible",
)

# The scopes below a phase: the stage loop opens one per middleware
# stage, named by ``Middleware.name``, directly inside
# ``tick/middleware``, and the fleet cache splits its per-key table
# writes from its snapshot push inside its stage.  The fault context
# splits its owner-table diff from the stages' remap invalidation.
STAGE_PHASE = "tick/middleware"
STAGE_SCOPES = ("scatter", "snapshot")
FAULT_PHASE = "tick/faults"
FAULT_SCOPES = ("inval", "remap")

# registered programs by HLO module name: the newest executable, the
# fingerprints of every program registered under the name, its map
_PROGRAMS: Dict[str, dict] = {}

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_WRAP = re.compile(r"[\w\-]+\(|\)")


def phase_of(op_name: str) -> Optional[Tuple[str, str]]:
    """(phase, sub-scope) of an instruction's ``op_name`` metadata, or
    None outside every phase.  Two phases have sub-scopes:
    ``tick/middleware``, the stage, and below it one of
    :data:`STAGE_SCOPES` where the stage opened it
    (``fleet_cache/scatter``); and ``tick/faults``, one of
    :data:`FAULT_SCOPES`.  Elsewhere the sub-scope is "".  The last
    component is the primitive's own name, never a scope.  Transform
    wrappers are read through: ``vmap(vmap(sweep/feasible))`` is
    ``sweep/feasible``."""
    parts = [p for p in _WRAP.sub("", op_name).split("/") if p]
    for i in range(len(parts) - 1):
        phase = parts[i] + "/" + parts[i + 1]
        if phase in PROGRAM_PHASES:
            rest = parts[i + 2:-1]
            sub = []
            if phase == STAGE_PHASE:
                sub = rest[:1]
                if rest[1:2] and rest[1] in STAGE_SCOPES:
                    sub.append(rest[1])
            elif phase == FAULT_PHASE:
                sub = [p for p in rest[:1] if p in FAULT_SCOPES]
            return phase, "/".join(sub)
    return None


def parse_phases(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (phase, sub-scope)} of a compiled program's
    HLO text.  An instruction's own ``op_name`` decides; where it names
    no phase, a fusion takes the commonest phase of what it fuses, and
    a loop or conditional that the compiler built with no ``op_name``
    (a scatter expanded into a while, a relayout loop) takes the
    commonest phase of its body and, where nothing in its body names a
    phase, that of its nearest users: the compiler built the loop for
    what reads its result, and the element reads of a scatter's loop
    carry the scatter's own ``op_name``.  An instruction inside a
    computation that a phased loop, conditional or call runs takes that
    caller's phase."""
    comps: Dict[str, List[str]] = {}
    own: Dict[str, Tuple[str, str]] = {}
    callees: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    fusions, built = set(), set()
    body: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        text = line.strip()
        if " = " not in text:
            if text.endswith("{"):
                head = text.split()[1 if text.startswith("ENTRY") else 0]
                body = comps.setdefault(head.lstrip("%"), [])
            continue
        name, rhs = text.split(" = ", 1)
        name = name[5:] if name.startswith("ROOT ") else name
        name = name.lstrip("%")
        if body is not None:
            body.append(name)
        m = _OP_NAME.search(rhs)
        if m:
            ph = phase_of(m.group(1))
            if ph is not None:
                own[name] = ph
        called = _CALLED.findall(rhs)
        for group in _BRANCHES.findall(rhs):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        if called:
            callees[name] = called
            if " fusion(" in rhs:
                fusions.add(name)
            elif not m:
                built.add(name)
        operands[name] = _REF.findall(rhs.split(", metadata=", 1)[0])
    users: Dict[str, List[str]] = {}
    for name, refs in operands.items():
        for r in refs:
            if r in operands:
                users.setdefault(r, []).append(name)

    def vote(names) -> Optional[Tuple[str, str]]:
        c = Counter(out[n] for n in names if n in out)
        return c.most_common(1)[0][0] if c else None

    def user_vote(name: str) -> Optional[Tuple[str, str]]:
        # the nearest users with a phase, looking through the tuples,
        # element reads and fusions between a loop and its consumers
        seen, near = {name}, users.get(name, [])
        for _ in range(8):
            ph = vote(near)
            if ph is not None or not near:
                return ph
            seen.update(near)
            near = [v for u in near if u not in out
                    for v in users.get(u, ()) if v not in seen]
        return None

    out = dict(own)
    changed = True
    while changed:
        changed = False
        for name, called in callees.items():
            if name not in out and (name in fusions or name in built):
                inner = [i for c in called for i in comps.get(c, ())]
                ph = vote(inner)
                if ph is None and name in built:
                    ph = user_vote(name)
                if ph is not None:
                    out[name] = ph
                    changed = True
            if name in out and name not in fusions:
                for c in called:
                    for i in comps.get(c, ()):
                        if i not in out:
                            out[i] = out[name]
                            changed = True
    return out


def register_program(compiled) -> None:
    """Keep a compiled program (``jax.stages.Compiled``) so that
    :func:`phase_map` can read it later.  Only its runtime executable
    is held, never an argument or a constant; under one module name
    the newest program is kept."""
    exe = compiled.runtime_executable()
    name = exe.hlo_modules()[0].name
    ent = _PROGRAMS.setdefault(name, {"fingerprints": set()})
    ent["fingerprints"].add(getattr(exe, "fingerprint", None) or id(exe))
    ent["exe"] = exe
    ent["map"] = None


def phase_map() -> Dict[str, Optional[Dict[str, Tuple[str, str]]]]:
    """{module name: {instruction name: (phase, sub-scope)}} of every
    registered program, parsed on first ask.  A name under which two
    different programs were registered maps to None: an op in a trace
    could be either's."""
    out = {}
    for name, ent in _PROGRAMS.items():
        if len(ent["fingerprints"]) > 1:
            out[name] = None
            continue
        if ent["map"] is None:
            ent["map"] = parse_phases(ent["exe"].get_hlo_text())
        out[name] = ent["map"]
    return out


# ---------------------------------------------------------------------------
# Reading + validation (the --check side)
# ---------------------------------------------------------------------------


def read_trace(path) -> List[dict]:
    """Parse a JSONL trace.  A truncated FINAL line (the process was
    killed mid-write, e.g. a CI timeout) is tolerated and dropped —
    flight-recorder semantics; truncation anywhere else is malformed
    and raises ``ValueError``."""
    lines = Path(path).read_text().splitlines()
    events = []
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            events.append(json.loads(line))
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                break  # torn final line: drop it
            raise ValueError(
                f"{path}: malformed JSONL at line {i + 1}"
            ) from None
    return events


def validate_events(events: List[dict]) -> List[str]:
    """Schema problems in a parsed event list (empty list = valid)."""
    problems = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        missing = [k for k in REQUIRED_KEYS if k not in ev]
        if missing:
            problems.append(f"event {i}: missing keys {', '.join(missing)}")
            continue
        if ev["ph"] not in PHASES:
            problems.append(f"event {i}: unknown phase {ev['ph']!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            problems.append(f"event {i}: bad ts {ev['ts']!r}")
        if ev["ph"] == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i}: bad dur {dur!r}")
    return problems
