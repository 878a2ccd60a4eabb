"""Where the engine's time goes: phase spans on two clocks, what it handed the device and waited for, three rings.

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

The record also follows the boundary between the engine and its device. Every program or transfer the engine
thread hands the runtime goes through :meth:`EngineLog.dispatch`, by the program's name; every wait for a
device result goes through :meth:`EngineLog.fetch`, by what was waited for (:data:`WAITS`), split into the
result's arrival and its copy to the host. Between the two the log asks the newest output whether it is ready
(at every phase switch): from the moment it is seen ready to the next dispatch the device had nothing of this
engine's to run, and those seconds are charged to the phase the engine thread was in (``starved_s``, a lower
bound of the device's idle time: the device may have run dry before the host looked). A pass of
:data:`SLOW_ITERATION_S` or more keeps its evidence in a ring of its own (:class:`SlowIteration`).

One :class:`EngineLog` belongs to one engine (each replica of a ``ReplicaSet`` has its own). It keeps the
newest iterations and the newest finished requests in two rings of fixed capacity and cumulative totals for
``stats()["loop"]``; slow iterations go to a third, smaller ring that the iterations' turnover does not touch.
Engines register their log process-wide when their thread starts
(:func:`engine_logs`, the kind of handle :func:`~unionml_tpu.observability.recorder.active_recorder` is), so
the records stay readable after ``close()``: by a post-mortem, by ``GET /debug/engine``, by a benchmark's
readers once the engine is freed.

Thread model: the phase clock and the iteration's tallies are touched by the engine thread alone; the rings
and totals are guarded by the log's own lock (one acquisition an iteration and one a finished request).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from types import MappingProxyType
from typing import Any, Callable, Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from unionml_tpu._logging import logger

__all__ = [
    "PHASES", "SLOW_ITERATION_S", "SPAN_PREFIX", "WAITS", "EngineLog", "IterationRecord", "RequestRecord",
    "SlowIteration", "engine_logs", "register_engine_log",
]

#: the phases of one engine iteration, in the order a pass meets them (docs/observability.md names what each covers)
PHASES = ("schedule", "admit", "grow", "dispatch", "fetch", "emit")
#: a phase's span on the profiler's clock is ``SPAN_PREFIX + phase``; the wait for work is ``SPAN_PREFIX + "idle"``
SPAN_PREFIX = "unionml_tpu.engine."
_INDEX = {name: i for i, name in enumerate(PHASES)}
_ADMIT, _FETCH = _INDEX["admit"], _INDEX["fetch"]
_IDLE = len(PHASES)
#: what the engine thread waits for inside ``fetch``: a decode dispatch's tokens, log-probabilities and done flags; an
#: admission's first token (and its prefill counters); that token's log-probability; the speculative round's reads;
#: an export admission's first token and row length
WAITS = ("decode", "first_token", "first_logprob", "spec", "export")
_WAIT_INDEX = {name: i for i, name in enumerate(WAITS)}
_NO_WAITS = (0.0,) * len(WAITS)
_NO_PHASES = (0.0,) * len(PHASES)
_NOTHING: Mapping[str, int] = MappingProxyType({})
#: a pass of this many seconds or more keeps its evidence (the cells' iterations take 0.12-0.32 s; the stall
#: ROADMAP S12 names takes 1.9-3.1 s)
SLOW_ITERATION_S = 1.0
_SPAN_NAMES = tuple(SPAN_PREFIX + name for name in PHASES) + (SPAN_PREFIX + "idle",)

#: records each ring retains: some minutes of a fast engine, most of an hour at an iteration a second
DEFAULT_CAPACITY = 4096
#: slow iterations an engine retains
SLOW_CAPACITY = 64
#: iterations before a slow one that its record sums up (admissions, dispatches, seconds)
_RECENT = 64
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
    admit_dispatches: int  #: of ``dispatched``, those inside ``admit`` (set-ups, chunks, first tokens, pastes)
    # fields after this line have defaults: a reader that builds a record by position keeps its ten
    dispatched: Mapping[str, int] = _NOTHING  #: programs and transfers handed the runtime, by the program's name
    wait_s: Tuple[float, ...] = _NO_WAITS  #: seconds waited for a device result, by :data:`WAITS`; their sum is ``phase_s[fetch]``
    wait_copy_s: Tuple[float, ...] = _NO_WAITS  #: of ``wait_s``, the copy to the host after the result was there
    starved_s: Tuple[float, ...] = _NO_PHASES  #: seconds the device had nothing of the engine's to run, by :data:`PHASES`
    #: prefill programs handed the runtime (chunks of any width, and monolithic prefills): each streams the weights
    #: once, so ``prefill_tokens / prefill_calls`` is the columns a stream of them carried
    prefill_calls: int = 0

    def render(self) -> Dict[str, Any]:
        out = self._asdict()
        out["phase_s"] = dict(zip(PHASES, self.phase_s))
        out["dispatched"] = dict(self.dispatched)
        out["wait_s"] = dict(zip(WAITS, self.wait_s))
        out["wait_copy_s"] = dict(zip(WAITS, self.wait_copy_s))
        out["starved_s"] = dict(zip(PHASES, self.starved_s))
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


class SlowIteration(NamedTuple):
    """The evidence of one pass that took :data:`SLOW_ITERATION_S` or more."""

    iteration: IterationRecord
    #: the pass's dispatches ``(name, at_s, call_s, None)`` and waits ``(kind, at_s, ready_s, copy_s)`` in order:
    #: ``at_s`` from the pass's start; ``call_s`` until the runtime's call returned; ``ready_s`` until the result was
    #: there (the device and its queue), ``copy_s`` its transfer to the host and conversion
    events: Tuple[Tuple[str, float, float, Optional[float]], ...]
    process_cpu_s: float  #: ``time.process_time()`` over the pass: none through a long wait means blocked below Python
    thread_cpu_s: float  #: ``time.thread_time()`` of the engine thread over the pass
    compiles: int  #: backend compiles in the process during the pass
    compile_s: float  #: and their seconds
    memory_before: Mapping[str, int]  #: the allocator's newest sample from before the pass (nothing on a CPU)
    memory_after: Mapping[str, int]  #: and at its end
    #: the up to 64 iterations before it, summed: ``iterations``, ``seconds`` (first start to this start),
    #: ``admitted``, ``finished``, ``dispatches``
    recent: Mapping[str, float]

    def render(self) -> Dict[str, Any]:
        out = self._asdict()
        out["iteration"] = self.iteration.render()
        out["events"] = [
            {"dispatch": what, "at_s": at, "call_s": a} if b is None else {"wait": what, "at_s": at, "ready_s": a, "copy_s": b}
            for what, at, a, b in self.events
        ]
        for key in ("memory_before", "memory_after", "recent"):
            out[key] = dict(out[key])
        return out


class _Compiles:
    """Backend compiles of the process and their seconds, from JAX's own monitoring events: one listener a
    process, registered with the first :class:`EngineLog` (a listener cannot be taken back)."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()  # the listener runs on whichever thread compiled
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += seconds


_compiles: Optional[_Compiles] = None


class _Phase:
    """What :meth:`EngineLog.phase` returns: enters the phase, and on exit resumes the one around it."""

    __slots__ = ("_log", "_index")

    def __init__(self, log: "EngineLog", index: int):
        self._log = log
        self._index = index

    def __enter__(self) -> None:
        self._log._enter(self._index)

    def __exit__(self, *exc_info: Any) -> None:
        self._log._leave()


class EngineLog:
    """Phase clock, dispatch and wait record, three rings and cumulative totals of one engine."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError("engine log capacity must be >= 1")
        global _compiles
        with _logs_lock:
            if _compiles is None:
                _compiles = _Compiles()
        self._compiles = _compiles
        self.capacity = capacity
        self._lock = threading.Lock()
        self._iterations: "deque[IterationRecord]" = deque(maxlen=capacity)
        self._requests: "deque[RequestRecord]" = deque(maxlen=capacity)
        self._slow: "deque[SlowIteration]" = deque(maxlen=SLOW_CAPACITY)
        with self._lock:
            self._zero_totals_locked()
        self._epoch = 0  # bumped by clear(): a pass that began before it is not recorded
        # the pass in progress: engine thread only
        self._pass_epoch = 0
        self._t0: Optional[float] = None
        self._mark = 0.0
        self._stack: List[int] = [0]  # phases entered and not left; the bottom is the pass's own ``schedule``
        self._current = 0  # the phase being charged: the top of the stack, or ``idle``
        self._span: Any = None
        self._dur = [0.0] * (len(PHASES) + 1)
        self._starved = [0.0] * (len(PHASES) + 1)
        self._dispatched: Dict[str, int] = {}
        self._admit_dispatches = 0
        self._wait = [0.0] * len(WAITS)
        self._wait_copy = [0.0] * len(WAITS)
        self._events: List[Tuple[str, float, float, Optional[float]]] = []
        self._cpu0 = (0.0, 0.0)  # process and thread CPU seconds at the pass's start
        self._compiles0 = (0, 0.0)
        # the device's queue as the engine thread knows it: one leaf of the newest output while that may still be
        # in flight, else the moment the queue was seen empty (None: not known to be empty)
        self._leaf: Any = None
        self._leaf_at: Dict[str, int] = {}  # by program: the index of its smallest array among its output's leaves
        self._dry_since: Optional[float] = None
        self._device: Any = None  # the first device of the first output: whose allocator is sampled
        self._memory: Mapping[str, int] = _NOTHING  # the allocator's newest sample: the end of the last pass
        #: the pass's tallies, bumped by the engine where the work happens and reset by :meth:`end`
        self.rows = 0
        self.prefill_tokens = 0
        self.admitted = 0
        self.finished = 0
        self.blocks_grown = 0
        self.table_syncs = 0
        self.prefill_calls = 0
        #: how the engine's decode program reads its paged cache (``"paged_kernel"`` / ``"gather"``), set by the
        #: engine after a decode dispatch; a fact about the program, not a counter: :meth:`clear` leaves it
        self.decode_attention_path: Optional[str] = None
        #: how it updates a recurrent state a slot (``"state_kernel"`` / ``"state_xla"``; None for a model with
        #: none), set beside it
        self.decode_state_path: Optional[str] = None
        #: how its prefill reads a row too long to read whole (``"chunk_kernel"`` / ``"chunk_xla"``; None where every
        #: row is read whole), set beside them
        self.chunk_attention_path: Optional[str] = None
        #: what the served model counted over the engine's dispatches (routed-expert pairs, window pages not
        #: read, ...: the names its ``counters`` attribute declares), cumulative, by kind of dispatch
        #: (``"decode"`` / ``"prefill"``); ``max_*`` hold the largest seen. Empty for a model that counts
        #: nothing. Fed by :meth:`count`, zeroed by :meth:`clear`
        self.model_counters: Dict[str, Dict[str, int]] = {}

    def _zero_totals_locked(self) -> None:
        """The cumulative side (caller holds the lock)."""
        self._count = 0
        self._idle_s = 0.0
        self._phase_s = [0.0] * len(PHASES)
        self._table_syncs = 0
        self._total_admit_dispatches = 0
        self._total_dispatched: Dict[str, int] = {}
        self._total_wait = [0.0] * len(WAITS)
        self._total_wait_copy = [0.0] * len(WAITS)
        self._total_starved = [0.0] * len(PHASES)
        self._total_compiles = 0
        self._total_compile_s = 0.0
        self._slow_count = 0
        self._prefill_widths: Dict[int, int] = {}

    def prefill_call(self, chunk: Optional[int] = None) -> None:
        """One prefill program was handed the runtime (engine thread): a call on the pass's record, and a chunk of
        ``chunk`` columns on the cumulative count of chunk calls by width too (a monolithic prefill passes none)."""
        self.prefill_calls += 1
        if chunk is not None:
            with self._lock:
                self._prefill_widths[chunk] = self._prefill_widths.get(chunk, 0) + 1

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

    def _switch(self, index: int) -> float:
        now = time.monotonic()
        self._dur[self._current] += now - self._mark
        self._mark = now
        self._look(now)
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._current = index
        self._span = jax.profiler.TraceAnnotation(_SPAN_NAMES[index])
        self._span.__enter__()
        return now

    def _enter(self, index: int) -> float:
        self._stack.append(index)
        return self._switch(index)

    def _leave(self) -> float:
        self._stack.pop()
        return self._switch(self._stack[-1])

    def _look(self, now: float) -> None:
        """Charge the phase in progress the seconds the device has had nothing to run, or, while the newest output
        may still be in flight, ask it: seen ready, the queue is empty from now on (the engine thread is its
        device's only dispatcher, and the device runs what it is handed in order)."""
        since = self._dry_since
        if since is not None:
            self._starved[self._current] += now - since
            self._dry_since = now
        elif self._leaf is not None and self._leaf.is_ready():
            self._leaf = None
            self._dry_since = now

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
        self._starved = [0.0] * (len(PHASES) + 1)
        if self._dry_since is not None:
            self._dry_since = now  # a device that ran dry while there was no work was not starved
        self._dispatched = {}
        self._admit_dispatches = 0
        self._wait = [0.0] * len(WAITS)
        self._wait_copy = [0.0] * len(WAITS)
        self._events = []
        self._cpu0 = (time.process_time(), time.thread_time())
        self._compiles0 = (self._compiles.count, self._compiles.seconds)
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

    def dispatch(self, name: str, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> Any:
        """Hand the runtime one program or transfer, ``fn(*args, **kwargs)``, under the program's name (its XLA
        module's, as a device trace prints it): tallied on the pass's record, and what it returns is what the log
        asks from now on whether the device still has work. The leaf kept is the output's smallest, and it is
        dropped here, before ``fn`` runs: it neither keeps a buffer alive past the next dispatch nor is it asked
        after a later program took it by donation."""
        now = time.monotonic()
        if self._dry_since is not None:
            self._starved[self._current] += now - self._dry_since
            self._dry_since = None
        self._leaf = None
        self._dispatched[name] = self._dispatched.get(name, 0) + 1
        if self._current == _ADMIT:
            self._admit_dispatches += 1
        out = fn(*args, **kwargs)
        if self._t0 is not None:
            self._events.append((name, now - self._t0, time.monotonic() - now, None))
        leaves = jax.tree_util.tree_leaves(out)
        # where a program's smallest output lies is found once: sizing every leaf costs ten times the flatten
        at = self._leaf_at.get(name)
        if at is None or at >= len(leaves) or not hasattr(leaves[at], "is_ready"):
            arrays = (i for i, leaf in enumerate(leaves) if hasattr(leaf, "is_ready"))
            at = min(arrays, key=lambda i: leaves[i].nbytes, default=None)
            if at is not None:
                self._leaf_at[name] = at
        self._leaf = None if at is None else leaves[at]  # none: the queue stays "not known to be empty" until the next output
        if self._device is None and isinstance(self._leaf, jax.Array):
            self._device = min(self._leaf.devices(), key=lambda d: d.id)
        return out

    def fetch(self, kind: str, *arrays: Any) -> Tuple[np.ndarray, ...]:
        """Wait for device results and bring them to the host, inside ``fetch``: the only place the engine thread
        waits for its device. ``kind`` is one of :data:`WAITS`. The copies to the host are started at once, for all
        the arrays, and the wait is stamped twice: when the first array is there (``ready``: the device and its
        queue; the others are outputs of the same program or of earlier ones), and when all are numpy arrays
        (``copy``: what was left of the transfers, and the conversion)."""
        which = _WAIT_INDEX[kind]
        start = ready = self._enter(_FETCH)
        try:
            for array in arrays:  # every copy follows its result without a turn of the host in between
                if hasattr(array, "copy_to_host_async"):  # an imported handoff's first token is a host value
                    array.copy_to_host_async()
            if hasattr(arrays[0], "block_until_ready"):
                arrays[0].block_until_ready()
            ready = time.monotonic()
            return tuple(np.asarray(a) for a in arrays)
        finally:
            done = self._leave()
            self._wait[which] += done - start
            self._wait_copy[which] += done - ready
            if self._t0 is not None:
                self._events.append((kind, start - self._t0, ready - start, done - ready))

    def end(self) -> None:
        """Bottom of the loop: the pass is recorded as one iteration and its tallies reset; one that took
        :data:`SLOW_ITERATION_S` or more also leaves a :class:`SlowIteration` and one line in the package's log."""
        now = time.monotonic()
        self._dur[self._current] += now - self._mark
        self._look(now)
        self._close_span()
        phase_s = tuple(self._dur[: len(PHASES)])
        table_syncs, admit_dispatches, dispatched = self.table_syncs, self._admit_dispatches, self._dispatched
        wait_s, wait_copy_s, starved_s = tuple(self._wait), tuple(self._wait_copy), tuple(self._starved[: len(PHASES)])
        tail = (
            self._t0, phase_s, self.rows, self.prefill_tokens, self.admitted, self.finished, self.blocks_grown,
            table_syncs, admit_dispatches, MappingProxyType(dispatched), wait_s, wait_copy_s, starved_s,
            self.prefill_calls,
        )
        compiles = self._compiles.count - self._compiles0[0]
        compile_s = self._compiles.seconds - self._compiles0[1]
        memory_before = self._memory
        if self._device is not None:
            # every pass: the call costs 1.1 us on a v5e (docs/observability.md), and the sample at one pass's end is
            # the next one's "before"
            self._memory = self._device.memory_stats() or _NOTHING
        evidence = None
        if now - self._t0 >= SLOW_ITERATION_S:
            evidence = (
                tuple(self._events), time.process_time() - self._cpu0[0], time.thread_time() - self._cpu0[1],
                compiles, compile_s, memory_before, self._memory,
            )
        epoch = self._pass_epoch
        self._t0 = None
        self.rows = self.prefill_tokens = self.admitted = self.finished = self.blocks_grown = self.table_syncs = 0
        self.prefill_calls = 0
        kept: Optional[SlowIteration] = None
        with self._lock:
            if epoch != self._epoch:
                return  # cleared while this pass ran: it belongs to what was forgotten
            record = IterationRecord(self._count, *tail)
            if evidence is not None:
                recent = list(self._iterations)[-_RECENT:]
                kept = SlowIteration(record, *evidence, MappingProxyType({
                    "iterations": len(recent), "seconds": record.start - recent[0].start if recent else 0.0,
                    "admitted": sum(r.admitted for r in recent), "finished": sum(r.finished for r in recent),
                    "dispatches": sum(sum(r.dispatched.values()) for r in recent),
                }))
                self._slow.append(kept)
                self._slow_count += 1
            self._iterations.append(record)
            self._count += 1
            self._table_syncs += table_syncs
            self._total_admit_dispatches += admit_dispatches
            for name, n in dispatched.items():
                self._total_dispatched[name] = self._total_dispatched.get(name, 0) + n
            for totals, seconds in (
                (self._phase_s, phase_s), (self._total_wait, wait_s), (self._total_wait_copy, wait_copy_s),
                (self._total_starved, starved_s),
            ):
                for i, s in enumerate(seconds):
                    totals[i] += s
            self._total_compiles += compiles
            self._total_compile_s += compile_s
        if kept is not None:
            # a pass that compiled is slow by design (a cold warm-up compiles through the loop): said, not warned of
            logger.log(logging.INFO if compiles else logging.WARNING, json.dumps({"slow_iteration": kept.render()}))

    def stop(self) -> None:
        """The engine thread is leaving its loop: close whatever span is open (the pass in progress, if any, is dropped)."""
        self._close_span()
        self._t0 = None
        self._leaf = None

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
            self._slow.clear()
            self._zero_totals_locked()
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
                "admit_dispatches": self._total_admit_dispatches,
                "dispatched": dict(self._total_dispatched),
                "wait_s": dict(zip(WAITS, self._total_wait)),
                "wait_copy_s": dict(zip(WAITS, self._total_wait_copy)),
                "starved_s": dict(zip(PHASES, self._total_starved)),
                "compiles": self._total_compiles,
                "compile_s": self._total_compile_s,
                "slow_iterations": self._slow_count,
            }

    def prefill_widths(self) -> Dict[int, int]:
        """Chunked prefill calls by their columns, cumulative, narrowest first (``stats()["prefill_widths"]``)."""
        with self._lock:
            return dict(sorted(self._prefill_widths.items()))

    def iteration_records(self) -> List[IterationRecord]:
        """The retained iterations, oldest first."""
        with self._lock:
            return list(self._iterations)

    def request_records(self) -> List[RequestRecord]:
        """The retained life-cycle records, oldest first."""
        with self._lock:
            return list(self._requests)

    def slow_iterations(self) -> List[SlowIteration]:
        """The retained slow iterations, oldest first."""
        with self._lock:
            return list(self._slow)

    def snapshot(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /debug/engine`` view of this engine: totals plus the newest ``limit`` records of each ring,
        newest first."""
        rings = [self.iteration_records(), self.request_records(), self.slow_iterations()]
        if limit is not None:
            rings = [ring[-limit:] if limit > 0 else [] for ring in rings]
        iterations, requests, slow = ([record.render() for record in reversed(ring)] for ring in rings)
        return {
            "capacity": self.capacity,
            "decode_attention_path": self.decode_attention_path,
            "decode_state_path": self.decode_state_path,
            "chunk_attention_path": self.chunk_attention_path,
            **({"model_counters": {k: dict(v) for k, v in self.model_counters.items()}} if self.model_counters else {}),
            **self.totals(),
            "prefill_widths": self.prefill_widths(),
            "iterations_log": iterations,
            "requests_log": requests,
            "slow_iterations_log": slow,
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
