"""Hierarchical spans: the zero-dependency tracer of the observability
plane, and the per-analysis self-time profile built on it.

Every :func:`span` times itself, whether or not tracing is on, and
keeps a thread-local sum of the time its children took.  On exit it
adds its *self* time (duration minus children) to the record of the
analysis open on that thread.  The root span of an analysis
(:func:`analysis`, ``cli.analyze`` for ``myth analyze``) opens the
record and closes it into a bounded ring that
:func:`analysis_profiles` reads: wall, self seconds by span name, host
waits on the device by site (:func:`wait`), compile seconds, the
async prefetch's seconds in flight and the prefetches cut between
rounds, program counts, and the span count.
The self seconds of one record add up to its wall: each span's time is
counted once, in the innermost span that covers it.

One process-wide :class:`Tracer` additionally records every span as a
completed ``ph: "X"`` event, plus *instant events* (watchdog trips,
fault injections, demotions, checkpoint writes) and counter tracks,
when tracing is on (``myth analyze --trace-out FILE``,
``MYTHRIL_TPU_TRACE=1``), and exports them as Chrome/Perfetto
``trace_event`` JSON (open at https://ui.perfetto.dev).  The kill
switch ``MYTHRIL_TPU_TRACE=0`` wins over every programmatic
``enable()``; it turns off events, not the self-time profile.

While a ``jax.profiler`` trace is recording, every span also opens a
profiler ``TraceMe`` under its own name, so the program's spans sit on
the profiler's host plane, on the same clock as the device's ops.  The
``TraceMe`` class is looked up only once jax has been imported by
someone else: this package imports only the stdlib.

Threads: span stacks and the open record are thread-local.  A thread
that does work another thread waits for (the supervised dispatch
worker of resilience/watchdog.py) borrows that thread's open span and
record (:func:`lend` / :func:`borrow`), so its spans nest under the
waiting span and count in the same analysis.  Any other thread (the
async prefetch worker) keeps its spans out of every record.

Sinks: call sites that feed a wall-clock counter (the
``SolverStatistics`` split, ``DispatchStats.device_s``) pass
``sink=(obj, field)``; the span adds its duration there on exit — one
clock pair, every consumer.
"""

import json
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional

#: event-buffer cap (events beyond it are dropped, counted, and still
#: totaled); override with MYTHRIL_TPU_TRACE_CAP
TRACE_CAP = 200_000
#: closed analysis records kept for :func:`analysis_profiles`; the
#: oldest is dropped first
PROFILE_CAP = 1024

_perf_ns = time.perf_counter_ns


def _kill_switched() -> bool:
    return os.environ.get("MYTHRIL_TPU_TRACE", "").lower() in (
        "0", "off", "false",
    )


def _env_cap() -> int:
    try:
        return max(1024, int(os.environ.get("MYTHRIL_TPU_TRACE_CAP",
                                            TRACE_CAP)))
    except ValueError:
        return TRACE_CAP


# -- per-thread state ----------------------------------------------------

#: thread ident -> that thread's span stack (the list object itself,
#: registered once per thread; the /debug/requests endpoint reads it)
_all_stacks: Dict[int, list] = {}


class _ThreadState:
    """The open spans of one thread, the analysis record their self
    time goes to, and, on a borrowing thread, the lender's open span and
    the loan itself."""

    __slots__ = ("stack", "record", "base", "loan")

    def __init__(self):
        self.stack: list = []
        self.record: Optional["AnalysisRecord"] = None
        self.base: Optional["Span"] = None
        self.loan: Optional["Loan"] = None


class _Local(threading.local):
    def __init__(self):
        self.state = _ThreadState()
        _all_stacks[threading.get_ident()] = self.state.stack


_local = _Local()


def _attribution(state: _ThreadState):
    """(enclosing span, open record) of a thread; both None when a loan
    the thread runs under was revoked."""
    loan = state.loan
    if loan is not None and not loan.live:
        return None, None
    stack = state.stack
    return (stack[-1] if stack else state.base), state.record


# -- profiler bridge -----------------------------------------------------

#: the profiler's TraceMe class once jax is loaded, False where it
#: cannot be had, None until jax has been imported
_trace_me = None


def _resolve_trace_me():
    global _trace_me
    if "jax" not in sys.modules:
        return None
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 — no profiler: spans stay host-only
        _trace_me = False
    else:
        _trace_me = TraceAnnotation
    return _trace_me


# -- the analysis record -------------------------------------------------


class AnalysisRecord:
    """Where one analysis's wall went (see the module docstring)."""

    __slots__ = ("name", "trace_id", "wall_s", "self_ns", "wait_ns",
                 "counts", "spans", "prefetch_inflight_s", "_prefetch_since",
                 "prefetch_preempted")

    def __init__(self, name: str):
        self.name = name
        self.trace_id: Optional[str] = None
        self.wall_s = 0.0
        self.self_ns: Dict[str, int] = {}
        self.wait_ns: Dict[str, int] = {}
        self.counts: Dict[str, int] = {}
        self.spans = 0
        self.prefetch_inflight_s = 0.0
        self._prefetch_since: Optional[float] = None
        self.prefetch_preempted = 0

    def add(self, name: str, self_ns: int) -> None:
        table = self.self_ns
        table[name] = table.get(name, 0) + self_ns
        self.spans += 1

    def prefetch_launched(self) -> None:
        """An async prefetch this analysis launched is in flight from
        now (the dispatcher runs one at a time)."""
        with _record_lock:
            self._prefetch_since = time.perf_counter()

    def prefetch_done(self, preempted: bool = False) -> None:
        """Its kernel completed (or failed, or was ``preempted``: cut
        between two rounds): the seconds since the launch count in this
        record, also after the record closed."""
        with _record_lock:
            self.prefetch_preempted += preempted
            if self._prefetch_since is not None:
                self.prefetch_inflight_s += (time.perf_counter()
                                             - self._prefetch_since)
                self._prefetch_since = None

    def _prefetch_s(self) -> float:
        if self._prefetch_since is None:
            return self.prefetch_inflight_s
        return (self.prefetch_inflight_s + time.perf_counter()
                - self._prefetch_since)

    def as_dict(self) -> dict:
        self_s = {name: ns / 1e9 for name, ns in self.self_ns.items()}
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "wall_s": self.wall_s,
            "self_s": self_s,
            "wait_s": {site: ns / 1e9 for site, ns in self.wait_ns.items()},
            "compile_s": self_s.get("device.compile", 0.0),
            # a prefetch still in flight counts up to now
            "prefetch_inflight_s": self._prefetch_s(),
            "prefetch_preempted": self.prefetch_preempted,
            "fuse_skips": self.counts.get("fuse_skips", 0),
            "resident_iterations": self.counts.get("resident_iterations", 0),
            "spans": self.spans,
        }


_record_lock = threading.Lock()


class _Profiles:
    """The ring of closed records plus per-name self seconds summed
    over every record ever closed (the ``--metrics-out`` family)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.records = deque(maxlen=PROFILE_CAP)
        self.self_total_ns: Dict[str, int] = {}
        self.closed = 0
        self.spans = 0

    def close(self, record: AnalysisRecord) -> None:
        with self.lock:
            self.records.append(record)
            self.closed += 1
            self.spans += record.spans
            totals = self.self_total_ns
            for name, ns in record.self_ns.items():
                totals[name] = totals.get(name, 0) + ns


_profiles = _Profiles()


# -- spans ---------------------------------------------------------------


class Span:
    """A named, timed region: times itself, nests per thread, adds its
    self time to the open analysis record and its duration to ``sink``
    (``(obj, field)``: ``field += elapsed`` on exit, a wall-clock
    counter fed by the same clock pair), and lands on the timeline when
    tracing is on.  ``span(name, sink=None, cat="pipeline", **attrs)``
    is this class."""

    __slots__ = ("name", "cat", "_sink", "_attrs", "_state", "_t0",
                 "_child", "_tm", "elapsed_s")

    def __init__(self, name, sink=None, cat="pipeline", **attrs):
        self.name = name
        self.cat = cat
        self._sink = sink
        self._attrs = attrs
        self._child = 0

    def __enter__(self):
        state = self._state = _local.state
        state.stack.append(self)
        tm = _trace_me
        if tm is None:
            tm = _resolve_trace_me()
        if tm and tm.is_enabled():
            self._tm = tm(self.name)
            self._tm.__enter__()
        else:
            self._tm = None
        self._t0 = _perf_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = _perf_ns() - self._t0
        if self._tm is not None:
            self._tm.__exit__(exc_type, exc, tb)
        state = self._state
        stack = state.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        loan = state.loan
        if loan is None or loan.live:
            parent = stack[-1] if stack else state.base
            record = state.record
        else:
            parent = record = None
        if parent is not None:
            parent._child += dur
        if record is not None:
            table = record.self_ns
            table[self.name] = table.get(self.name, 0) + dur - self._child
            record.spans += 1
        self.elapsed_s = dur / 1e9
        if self._sink is not None:
            obj, field = self._sink
            setattr(obj, field, getattr(obj, field) + self.elapsed_s)
        tracer = _tracer
        if tracer.enabled:
            attrs = self._attrs
            if parent is not None or exc_type is not None:
                attrs = dict(attrs)
                if parent is not None:
                    attrs.setdefault("parent", parent.name)
                if exc_type is not None:
                    attrs["error"] = exc_type.__name__
            tracer._record_span(self.name, self.cat, self._t0, dur, attrs)
        return False


span = Span


class _WaitSpan(Span):
    """``device.wait``: its self time also goes to its site's total, and
    entering it calls the wait hook (:func:`set_wait_hook`)."""

    __slots__ = ()

    def __enter__(self):
        hook = _wait_hook
        if hook is not None:
            hook()
        return Span.__enter__(self)

    def __exit__(self, exc_type, exc, tb):
        Span.__exit__(self, exc_type, exc, tb)
        _, record = _attribution(self._state)
        if record is not None:
            site = self._attrs["site"]
            waits = record.wait_ns
            waits[site] = (waits.get(site, 0)
                           + round(self.elapsed_s * 1e9) - self._child)
        return False


class _AnalysisSpan(Span):
    """Root span of an analysis: opens this thread's record on entry
    (unless one is open already: then it is a plain span) and closes it
    into the ring on exit."""

    __slots__ = ("_record",)

    def __enter__(self):
        state = _local.state
        self._record = None
        if state.record is None:
            self._record = state.record = AnalysisRecord(self.name)
        return Span.__enter__(self)

    def __exit__(self, exc_type, exc, tb):
        Span.__exit__(self, exc_type, exc, tb)
        record = self._record
        if record is not None:
            record.wall_s = self.elapsed_s
            record.trace_id = _tracer.trace_id
            self._state.record = None
            _profiles.close(record)
        return False


class Loan:
    """A thread's open span and record, lent to a thread that works
    while the lender waits for it.  Revoked when the lender stops
    waiting (a watchdog deadline): the borrower's later spans then
    count nowhere."""

    __slots__ = ("parent", "record", "live")

    def __init__(self, parent, record):
        self.parent = parent
        self.record = record
        self.live = True

    def revoke(self) -> None:
        self.live = False


def lend() -> Optional[Loan]:
    """This thread's open span and record, for :func:`borrow` on the
    thread it is about to wait for (None when neither is open)."""
    parent, record = _attribution(_local.state)
    if parent is None and record is None:
        return None
    return Loan(parent, record)


def borrow(loan: Optional[Loan]) -> None:
    """Run this (fresh) thread's spans under ``loan``: they nest under
    the lender's open span and count in its analysis."""
    if loan is None:
        return
    state = _local.state
    state.base, state.record, state.loan = loan.parent, loan.record, loan


def wait(site: str, **attrs) -> Span:
    """A ``device.wait`` span: the thread blocks here on the device (a
    result fetch, an upload that can queue behind a running kernel) or
    on something it shares with the prefetch worker.  ``site`` names
    the place; the record keeps the wait seconds of each site."""
    return _WaitSpan("device.wait", cat="device", site=site, **attrs)


#: called, on the waiting thread, as each ``device.wait`` span opens
_wait_hook = None


def set_wait_hook(hook) -> None:
    """Call ``hook()`` on the waiting thread whenever a ``device.wait``
    span opens, tracing on or off (the async prefetch stops at its next
    round when a synchronous caller waits on the device); one hook per
    process, ``None`` removes it."""
    global _wait_hook
    _wait_hook = hook


def analysis(name: str = "cli.analyze", cat: str = "cli") -> Span:
    """The root span of one analysis: its record opens here and closes
    into :func:`analysis_profiles` when the span exits."""
    return _AnalysisSpan(name, cat=cat)


def complete_child(name: str, seconds: float, cat: str = "device") -> None:
    """Account ``seconds`` that just ended on this thread as a child
    span ``name`` of the innermost open span (a compile that
    ``jax.monitoring`` reports once it is over: ``device.compile``)."""
    dur = int(seconds * 1e9)
    parent, record = _attribution(_local.state)
    if parent is not None:
        parent._child += dur
    if record is not None:
        record.add(name, dur)
    tracer = _tracer
    if tracer.enabled:
        attrs = {"parent": parent.name} if parent is not None else None
        tracer._record_span(name, cat, _perf_ns() - dur, dur, attrs)


def record_count(key: str, amount: int = 1) -> None:
    """Add ``amount`` to count ``key`` of the analysis open on this
    thread (no-op outside an analysis)."""
    _, record = _attribution(_local.state)
    if record is not None:
        record.counts[key] = record.counts.get(key, 0) + amount


def current_analysis() -> Optional[AnalysisRecord]:
    """The record of the analysis open on this thread, if any."""
    return _attribution(_local.state)[1]


def analysis_profiles() -> List[dict]:
    """The closed analysis records, oldest first (at most
    ``PROFILE_CAP``)."""
    with _profiles.lock:
        records = list(_profiles.records)
    with _record_lock:
        return [record.as_dict() for record in records]


def self_seconds_totals() -> Dict[str, float]:
    """Self seconds by span name, summed over every closed record."""
    with _profiles.lock:
        return {name: ns / 1e9
                for name, ns in _profiles.self_total_ns.items()}


def profile_counts() -> Dict[str, int]:
    """Analyses closed and spans counted in them, process lifetime."""
    with _profiles.lock:
        return {"analyses": _profiles.closed, "spans": _profiles.spans}


# -- the event tracer ----------------------------------------------------


class Tracer:
    """Process-wide span/instant recorder (see module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()
        self._events = []
        self._cap = _env_cap()
        self._totals: Dict[str, float] = {}  # name -> cumulative seconds
        self._counts: Dict[str, int] = {}    # name -> completed spans
        # cross-process trace identity: minted at the CLI/serve edge,
        # propagated through the coalescer scope stamps and the fleet
        # lease protocol so one request renders as one Perfetto trace
        self.trace_id: Optional[str] = None
        # pids already claimed by absorbed worker streams: a respawned
        # fleet worker reusing an earlier worker's pid must not merge
        # into its predecessor's Perfetto track
        self._absorbed_pids: Dict[str, int] = {}
        self.span_count = 0
        self.instant_count = 0
        self.dropped = 0
        # enabled only on an explicit opt-in: env MYTHRIL_TPU_TRACE
        # truthy, --trace-out (observability.configure_from_cli), or a
        # programmatic enable().  The kill switch wins.
        env = os.environ.get("MYTHRIL_TPU_TRACE", "").lower()
        self.enabled = env in ("1", "on", "true") and not _kill_switched()

    # -- control -------------------------------------------------------

    def enable(self) -> bool:
        """Turn event recording on (False when the
        ``MYTHRIL_TPU_TRACE=0`` kill switch vetoes it)."""
        if _kill_switched():
            return False
        self.enabled = True
        return True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Drop events, totals, and counters; keeps enablement."""
        with self._lock:
            self._events = []
            self._totals = {}
            self._counts = {}
            self.span_count = 0
            self.instant_count = 0
            self.dropped = 0
            self._epoch_ns = time.perf_counter_ns()

    # -- recording -----------------------------------------------------

    def live_spans(self) -> Dict[int, str]:
        """Advisory snapshot of each thread's innermost open span name
        (the serve plane's ``/debug/requests`` phase field).  Reads the
        per-thread stacks without locking — a torn read can at worst
        name a span that just closed."""
        out = {}
        for tid, stack in list(_all_stacks.items()):
            try:
                out[tid] = stack[-1].name
            except IndexError:  # no open span, or it just closed
                pass
        return out

    def _append(self, event) -> None:
        # callers hold self._lock
        if len(self._events) < self._cap:
            self._events.append(event)
        else:
            self.dropped += 1

    def _record_span(self, name, cat, t0_ns, dur_ns, attrs) -> None:
        event = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,  # microseconds
            "dur": dur_ns / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if attrs:
            event["args"] = attrs
        with self._lock:
            self.span_count += 1
            self._totals[name] = self._totals.get(name, 0.0) + dur_ns / 1e9
            self._counts[name] = self._counts.get(name, 0) + 1
            self._append(event)
        from mythril_tpu.observability.flight import get_flight_recorder

        get_flight_recorder().record(event)

    def record_instant(self, name, cat, attrs) -> None:
        event = {
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "p",  # process-scoped instant marker
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if attrs:
            event["args"] = attrs
        with self._lock:
            self.instant_count += 1
            self._append(event)
        from mythril_tpu.observability.flight import get_flight_recorder

        get_flight_recorder().record(event)

    def record_counter(self, name: str, values: dict) -> None:
        """Perfetto counter track (``ph: "C"``): live lanes, frontier
        queue depth, resident-pool rows ride the trace as numeric
        series alongside the spans."""
        event = {
            "name": name,
            "cat": "counter",
            "ph": "C",
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {k: float(v) for k, v in values.items()},
        }
        with self._lock:
            self._append(event)

    def absorb_events(self, events: list, worker: Optional[str] = None,
                      trace_id: Optional[str] = None) -> int:
        """Merge pre-built trace events from another process (a fleet
        worker's span stream) into the buffer so ``--trace-out``
        renders one timeline.  Per-name *totals* are deliberately NOT
        updated: they describe THIS process's wall, and folding a
        worker's spans in would double-count time the coordinator spent
        waiting on it.

        ``worker`` names the stream: its events are re-pidded onto a
        synthetic pid unique to that worker, so a respawned worker that
        reuses an earlier worker's OS pid (pid recycling is routine
        under heavy respawn) cannot silently merge two workers' streams
        into one Perfetto track, and a ``process_name`` metadata event
        labels the track.  ``trace_id`` re-parents the stream under the
        request's trace identity (every absorbed event gains
        ``args.trace_id``).  Returns the number absorbed."""
        absorbed = 0
        remap_pid = None
        with self._lock:
            if worker is not None:
                remap_pid = self._absorbed_pids.get(worker)
                if remap_pid is None:
                    # own pid is reserved; synthetic pids grow downward
                    # from a range no OS hands out, one per worker name
                    remap_pid = 1_000_000 + len(self._absorbed_pids) + 1
                    self._absorbed_pids[worker] = remap_pid
                    if len(self._events) < self._cap:
                        label = f"fleet-worker {worker}"
                        if trace_id:
                            label += f" [trace {trace_id}]"
                        self._events.append({
                            "name": "process_name", "ph": "M",
                            "pid": remap_pid, "tid": 0,
                            "args": {"name": label},
                        })
            for event in events:
                if not isinstance(event, dict) or "ph" not in event:
                    continue
                if remap_pid is not None or trace_id is not None:
                    event = dict(event)
                    if remap_pid is not None:
                        event["pid"] = remap_pid
                    if trace_id is not None:
                        args = dict(event.get("args") or {})
                        args["trace_id"] = trace_id
                        event["args"] = args
                if len(self._events) < self._cap:
                    self._events.append(event)
                    self.span_count += int(event.get("ph") == "X")
                    self.instant_count += int(event.get("ph") == "i")
                    absorbed += 1
                else:
                    self.dropped += 1
        return absorbed

    def add_external_total(self, name: str, seconds: float) -> None:
        """Account wall-clock measured outside this process (a fleet
        worker's lease wall) under a span name, totals/counts only —
        feeds per-worker share reporting in scripts/profile_t3.py and
        the bench fleet microbench without fabricating timeline
        events."""
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1

    # -- export / aggregation ------------------------------------------

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def totals_snapshot(self) -> Dict[str, float]:
        """Per-name cumulative span seconds, children included, of the
        spans recorded while tracing was on (copy)."""
        with self._lock:
            return dict(self._totals)

    def counts_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def export_chrome(self, path: str) -> str:
        """Write the Chrome/Perfetto ``trace_event`` JSON.  The object
        form (``{"traceEvents": [...]}``) is used so metadata rides
        alongside without breaking loaders: ``otherData.analyses``
        holds the closed analysis records.  When the buffer cap
        dropped events, a ``trace.truncated`` instant marks the
        timeline itself — a consumer must not mistake a capped trace
        for a complete one (the registry's
        ``mythril_tpu_trace_dropped_events`` counter carries the same
        number)."""
        events = self.events()
        if self.dropped:
            last_ts = max(
                (e.get("ts", 0.0) for e in events if "ts" in e),
                default=0.0,
            )
            events.append({
                "name": "trace.truncated", "cat": "meta", "ph": "i",
                "s": "g", "ts": last_ts, "pid": os.getpid(), "tid": 0,
                "args": {"dropped_events": int(self.dropped),
                         "cap": int(self._cap)},
            })
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "mythril-tpu observability plane",
                "span_events": self.span_count,
                "instant_events": self.instant_count,
                "dropped_events": self.dropped,
                "trace_id": self.trace_id,
                "analyses": analysis_profiles(),
            },
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)
        return path


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def instant(name: str, cat: str = "event", **attrs) -> None:
    """Record an instant event (watchdog trip, fault, demotion,
    checkpoint write) on the timeline.  No-op when tracing is off."""
    tracer = _tracer
    if not tracer.enabled:
        return
    tracer.record_instant(name, cat, attrs)


def counter(name: str, **values) -> None:
    """Record a Perfetto counter-track sample (live lanes, frontier
    queue depth, pool rows).  No-op when tracing is off — one attribute
    check, same contract as :func:`instant`."""
    tracer = _tracer
    if not tracer.enabled:
        return
    tracer.record_counter(name, values)


def new_trace_id() -> str:
    """Mint a request/run trace identity (hex, collision-safe across
    hosts) — done once at the CLI or serve edge and propagated through
    coalescer scope stamps and the fleet lease protocol."""
    return uuid.uuid4().hex[:16]


def set_trace_id(trace_id: Optional[str]) -> None:
    _tracer.trace_id = trace_id


def get_trace_id() -> Optional[str]:
    return _tracer.trace_id


def traced(name: str, cat: str = "pipeline"):
    """Decorator form of :func:`span`."""

    def wrap(func):
        def inner(*args, **kwargs):
            with span(name, cat=cat):
                return func(*args, **kwargs)

        inner.__name__ = getattr(func, "__name__", name)
        inner.__doc__ = func.__doc__
        return inner

    return wrap


def totals_snapshot() -> Dict[str, float]:
    return _tracer.totals_snapshot()


def reset_for_tests() -> None:
    global _tracer, _profiles
    _tracer = Tracer()
    _profiles = _Profiles()
