"""Where the engine's time goes: phase spans on two clocks, an iteration ring, a request life-cycle ring.

The request timelines of :mod:`~unionml_tpu.observability.trace` say what happened to one request; they
exist only while tracing is on and say nothing about the engine thread itself. This module is the engine's
own record, always on: every pass of ``ContinuousBatcher._engine_loop`` is split into the six phases of
:data:`PHASES`, and each phase is written to two clocks at once —

- a ``jax.profiler.TraceAnnotation("unionml_tpu.engine.<phase>")`` span, which lands on the profiler's host
  plane beside the device's programs whenever a capture is running (``POST /debug/profile``, the benchmark's
  traced runs) and is a sub-microsecond no-op otherwise;
- its ``time.monotonic()`` duration, added to the current iteration's record.

The phases partition an iteration: the engine thread is in exactly one of them from the moment it finds work
to the moment it returns to the top of its loop (a nested phase suspends the one around it). Time spent
waiting with nothing to do is ``idle``, kept beside the phases and outside every iteration.

One :class:`EngineLog` belongs to one engine (each replica of a ``ReplicaSet`` has its own). It keeps the
newest iterations and the newest finished requests in two rings of fixed capacity and cumulative totals for
``stats()["loop"]``. Engines register their log process-wide when their thread starts
(:func:`engine_logs`, the kind of handle :func:`~unionml_tpu.observability.recorder.active_recorder` is), so
the records stay readable after ``close()``: by a post-mortem, by ``GET /debug/engine``, by a benchmark's
readers once the engine is freed.

Thread model: the phase clock and the iteration's tallies are touched by the engine thread alone; the rings
and totals are guarded by the log's own lock (one acquisition an iteration and one a finished request).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax

__all__ = ["PHASES", "SPAN_PREFIX", "EngineLog", "IterationRecord", "RequestRecord", "engine_logs", "register_engine_log"]

#: the phases of one engine iteration, in the order a pass meets them (docs/observability.md names what each covers)
PHASES = ("schedule", "admit", "grow", "dispatch", "fetch", "emit")
#: a phase's span on the profiler's clock is ``SPAN_PREFIX + phase``; the wait for work is ``SPAN_PREFIX + "idle"``
SPAN_PREFIX = "unionml_tpu.engine."
_INDEX = {name: i for i, name in enumerate(PHASES)}
_IDLE = len(PHASES)
_SPAN_NAMES = tuple(SPAN_PREFIX + name for name in PHASES) + (SPAN_PREFIX + "idle",)

#: records each ring retains: some minutes of a fast engine, most of an hour at an iteration a second
DEFAULT_CAPACITY = 4096
#: engine logs the process-wide handle retains (a fleet's replicas, and the engines closed before them)
_MAX_LOGS = 16


class IterationRecord(NamedTuple):
    """One pass of the engine loop that found work."""

    index: int  #: position in the engine's count of iterations (``RequestRecord.first_iteration`` points here)
    start: float  #: ``time.monotonic()`` when the engine thread found the work
    phase_s: Tuple[float, ...]  #: seconds in each of :data:`PHASES`, in that order; their sum is the pass's wall time
    rows: int  #: resident rows at the decode dispatch (0: no dispatch this pass)
    prefill_tokens: int  #: prompt positions run through prefill
    admitted: int  #: admissions completed (pasted into the pool, or exported)
    finished: int  #: rows that finished
    blocks_grown: int  #: KV blocks appended to residents' tables
    table_syncs: int  #: runs of the program that carries the host's table growths and slot releases to the device
    admit_dispatches: int  #: programs and transfers the admit phase handed the runtime (set-ups, chunks, first tokens, pastes)

    def render(self) -> Dict[str, Any]:
        out = self._asdict()
        out["phase_s"] = dict(zip(PHASES, self.phase_s))
        return out


class RequestRecord(NamedTuple):
    """The life cycle of one request, written once at its end. Times are ``time.monotonic()``."""

    request_id: Optional[str]  #: the id the HTTP layer echoes in ``X-Request-Id`` (None outside a request context)
    submitted: float
    admission_started: Optional[float]  #: a slot and its blocks were assigned (None: shed or cancelled while waiting)
    first_token: Optional[float]  #: the prompt-sampled token was handed to the stream
    finished: float
    prompt_tokens: int
    cached_tokens: int  #: prompt tokens served from the radix cache
    produced: int  #: tokens handed to the stream
    outcome: str  #: finish | cancel | shed_deadline | export | error | closed
    first_iteration: Optional[int]  #: index of the iteration that emitted the first token

    def render(self) -> Dict[str, Any]:
        return self._asdict()


class _Phase:
    """What :meth:`EngineLog.phase` returns: enters the phase, and on exit resumes the one around it."""

    __slots__ = ("_log", "_index")

    def __init__(self, log: "EngineLog", index: int):
        self._log = log
        self._index = index

    def __enter__(self) -> None:
        log = self._log
        log._stack.append(self._index)
        log._switch(self._index)

    def __exit__(self, *exc_info: Any) -> None:
        log = self._log
        log._stack.pop()
        log._switch(log._stack[-1])


class EngineLog:
    """Phase clock, iteration ring, request life-cycle ring and cumulative totals of one engine."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError("engine log capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._iterations: "deque[IterationRecord]" = deque(maxlen=capacity)
        self._requests: "deque[RequestRecord]" = deque(maxlen=capacity)
        # cumulative, under the lock
        self._count = 0
        self._idle_s = 0.0
        self._phase_s = [0.0] * len(PHASES)
        self._table_syncs = 0
        self._admit_dispatches = 0
        self._epoch = 0  # bumped by clear(): a pass that began before it is not recorded
        # the pass in progress: engine thread only
        self._pass_epoch = 0
        self._t0: Optional[float] = None
        self._mark = 0.0
        self._stack: List[int] = [0]  # phases entered and not left; the bottom is the pass's own ``schedule``
        self._current = 0  # the phase being charged: the top of the stack, or ``idle``
        self._span: Any = None
        self._dur = [0.0] * (len(PHASES) + 1)
        #: the pass's tallies, bumped by the engine where the work happens and reset by :meth:`end`
        self.rows = 0
        self.prefill_tokens = 0
        self.admitted = 0
        self.finished = 0
        self.blocks_grown = 0
        self.table_syncs = 0
        self.admit_dispatches = 0
        #: how the engine's decode program reads its paged cache (``"paged_kernel"`` / ``"gather"``), set by the
        #: engine after a decode dispatch; a fact about the program, not a counter: :meth:`clear` leaves it
        self.decode_attention_path: Optional[str] = None
        #: what the served model counted over the engine's dispatches (routed-expert pairs, window pages not
        #: read, ...: the names its ``counters`` attribute declares), cumulative, by kind of dispatch
        #: (``"decode"`` / ``"prefill"``); ``max_*`` hold the largest seen. Empty for a model that counts
        #: nothing. Fed by :meth:`count`, zeroed by :meth:`clear`
        self.model_counters: Dict[str, Dict[str, int]] = {}

    def count(self, kind: str, names: Sequence[str], values: Sequence[int]) -> None:
        """Add one dispatch's counts (engine thread)."""
        with self._lock:
            held = self.model_counters.setdefault(kind, {})
            for name, value in zip(names, values):
                held[name] = max(held.get(name, 0), int(value)) if name.startswith("max_") else held.get(name, 0) + int(value)

    def counted(self, names: Sequence[str], kind: Optional[str] = None) -> Dict[str, int]:
        """The counts under ``names`` (zero where nothing was counted yet), of one kind of dispatch or of all."""
        with self._lock:
            kinds = [self.model_counters.get(kind, {})] if kind else list(self.model_counters.values())
            return {
                n: (max if n.startswith("max_") else sum)([k.get(n, 0) for k in kinds] or [0]) for n in names
            }

    # ------------------------------------------------------------------ the engine thread's clock

    @property
    def index(self) -> int:
        """Index of the iteration in progress (the count of those recorded)."""
        return self._count

    def _switch(self, index: int) -> None:
        now = time.monotonic()
        self._dur[self._current] += now - self._mark
        self._mark = now
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._current = index
        self._span = jax.profiler.TraceAnnotation(_SPAN_NAMES[index])
        self._span.__enter__()

    def begin(self) -> None:
        """Top of the loop, and again whenever the engine wakes from a wait: an iteration starts here, in
        ``schedule``. What passed since an earlier ``begin`` that no :meth:`end` followed was idle."""
        now = time.monotonic()
        if self._t0 is not None:
            idle = now - self._t0
            with self._lock:
                self._idle_s += idle
        self._t0 = self._mark = now
        self._pass_epoch = self._epoch
        self._dur = [0.0] * (len(PHASES) + 1)
        self._stack = [0]
        self._current = 0
        self._switch(0)

    def wait(self) -> None:
        """About to wait for work: the open span closes and ``idle`` runs until the next :meth:`begin`."""
        self._switch(_IDLE)

    def phase(self, name: str) -> _Phase:
        """Context manager: the engine thread is in phase ``name`` inside it; the phase around it (``schedule``
        at the top of a pass) is suspended meanwhile, on both clocks."""
        return _Phase(self, _INDEX[name])

    def end(self) -> None:
        """Bottom of the loop: the pass is recorded as one iteration and its tallies reset."""
        now = time.monotonic()
        self._dur[self._current] += now - self._mark
        self._close_span()
        phase_s = tuple(self._dur[: len(PHASES)])
        table_syncs, admit_dispatches = self.table_syncs, self.admit_dispatches
        tail = (
            self._t0, phase_s, self.rows, self.prefill_tokens, self.admitted, self.finished, self.blocks_grown,
            table_syncs, admit_dispatches,
        )
        epoch = self._pass_epoch
        self._t0 = None
        self.rows = self.prefill_tokens = self.admitted = self.finished = self.blocks_grown = 0
        self.table_syncs = self.admit_dispatches = 0
        with self._lock:
            if epoch != self._epoch:
                return  # cleared while this pass ran: it belongs to what was forgotten
            self._iterations.append(IterationRecord(self._count, *tail))
            self._count += 1
            self._table_syncs += table_syncs
            self._admit_dispatches += admit_dispatches
            for i, seconds in enumerate(phase_s):
                self._phase_s[i] += seconds

    def stop(self) -> None:
        """The engine thread is leaving its loop: close whatever span is open (the pass in progress, if any, is dropped)."""
        self._close_span()
        self._t0 = None

    def _close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def clear(self) -> None:
        """Forget the records and zero the totals (any thread): an engine's warm-up passes, compiles and all,
        are not its traffic's."""
        with self._lock:
            self._iterations.clear()
            self._requests.clear()
            self._count = 0
            self._idle_s = 0.0
            self._phase_s = [0.0] * len(PHASES)
            self._table_syncs = 0
            self._admit_dispatches = 0
            self._epoch += 1
            self.model_counters = {}

    # ------------------------------------------------------------------ requests

    def request(self, record: RequestRecord) -> None:
        """A request ended (any thread)."""
        with self._lock:
            self._requests.append(record)

    # ------------------------------------------------------------------ readers (any thread)

    def totals(self) -> Dict[str, Any]:
        """Cumulative counters for ``stats()["loop"]``: numbers only, so the Prometheus exposition renders them."""
        with self._lock:
            return {
                "iterations": self._count,
                "idle_s": self._idle_s,
                "phase_s": dict(zip(PHASES, self._phase_s)),
                "table_syncs": self._table_syncs,
                "admit_dispatches": self._admit_dispatches,
            }

    def iteration_records(self) -> List[IterationRecord]:
        """The retained iterations, oldest first."""
        with self._lock:
            return list(self._iterations)

    def request_records(self) -> List[RequestRecord]:
        """The retained life-cycle records, oldest first."""
        with self._lock:
            return list(self._requests)

    def snapshot(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /debug/engine`` view of this engine: totals plus the newest ``limit`` records of each ring,
        newest first."""
        iterations, requests = self.iteration_records(), self.request_records()
        if limit is not None:
            iterations = iterations[-limit:] if limit > 0 else []
            requests = requests[-limit:] if limit > 0 else []
        return {
            "capacity": self.capacity,
            "decode_attention_path": self.decode_attention_path,
            **({"model_counters": {k: dict(v) for k, v in self.model_counters.items()}} if self.model_counters else {}),
            **self.totals(),
            "iterations_log": [record.render() for record in reversed(iterations)],
            "requests_log": [record.render() for record in reversed(requests)],
        }


_logs: "deque[EngineLog]" = deque(maxlen=_MAX_LOGS)
_logs_lock = threading.Lock()


def register_engine_log(log: EngineLog) -> None:
    """Called by an engine when its thread starts; the oldest of more than a fleet's worth of logs is dropped."""
    with _logs_lock:
        if not any(known is log for known in _logs):
            _logs.append(log)


def engine_logs() -> List[EngineLog]:
    """The logs of the engines that ran in this process, oldest first — live ones and closed ones alike."""
    with _logs_lock:
        return list(_logs)
