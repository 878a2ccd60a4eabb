"""Continuous (in-flight) batching for generation serving.

The reference serves predictions strictly one request at a time (an eager
``model.predict`` per HTTP call, unionml/fastapi.py:50-64); round 2's streaming
route inherited that shape — each ``/predict-stream`` request occupied the whole
decode loop. This module is the TPU-native fix: decode is weight-bandwidth
bound, so stepping a batch of S cache rows costs roughly the same HBM traffic
as stepping one — concurrent requests should share decode dispatches instead of
queueing behind each other.

Design (classic continuous batching, expressed in fixed XLA shapes):

- the engine owns one pool of KV pages per layer (``pool_blocks`` blocks of
  ``block_size`` positions), a block table row per slot (``[S, max_blocks]``)
  and the decode carry (``tok/lengths/done`` per slot) — all shapes static, so
  XLA compiles exactly one decode program and one admission program;
- **join at prefill**: an arriving prompt prefills through the Generator's own
  jitted prefill at batch 1 (same numerics, same bucket set) into a fresh
  ``[1, cache_len]`` row, which one jitted program lays as pages and writes
  whole into the blocks allocated to a free slot between decode chunks;
- **stall-free admission**: with ``admit_chunk`` set the admission prefill is
  sliced into fixed-size chunks through the Generator's chunked-prefill program
  and the engine alternates chunks with decode dispatches under a
  per-iteration ``prefill_budget`` (Sarathi-Serve's chunked-prefill scheduling,
  OSDI '24) — a long prompt no longer freezes resident streams for its whole
  prefill; their time-between-tokens is bounded by ~one chunk's dispatch;
- **shared decode**: a background engine thread repeatedly runs the Generator's
  one-compile ``lax.scan`` decode for ``decode_chunk`` steps over ALL slots and
  routes each row's new tokens to its request's queue — S concurrent streams,
  one device dispatch per chunk;
- **leave at eos/budget**: rows whose ``eos_id`` fired (device-side ``done``) or
  whose ``max_new_tokens`` budget is spent free their slot at the next chunk
  boundary; freed (and never-used) slots ride along masked — ``done`` rows emit
  pads, never advance their cache, and stay out of routed-expert capacity, the
  same contract the Generator uses for synthetic batch-padding rows.

Correctness: with greedy decoding each stream's tokens are EXACTLY what a
sequential ``Generator.__call__([prompt])`` produces (rows of a batch are
independent under the cache contract; tests pin this with concurrent vs
sequential equality). Sampled decoding draws from the same per-step policy
distribution but is not key-path-compatible with a solo run — the loop key is
shared by whoever is resident, so equality holds in distribution only.

Thread model: ``submit`` may be called from any thread (the serving app calls
it from executor threads); the engine thread is the only one touching device
state. Per-request iterators consume a ``queue.Queue`` and so compose directly
with the ``/predict-stream`` route's ``run_in_executor(next, iterator)`` —
register a stream predictor that returns ``batcher.submit(prompt)`` and
concurrent HTTP streams share dispatches with no route changes.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from unionml_tpu._logging import logger
from unionml_tpu.defaults import (
    SERVE_MAX_WAITING,
    serve_admit_chunk,
    serve_dp_replicas,
    serve_max_admissions,
    serve_prefill_budget,
    serve_prefix_cache,
    serve_replica_roles,
)
from unionml_tpu.observability.engine_log import EngineLog, RequestRecord, register_engine_log
from unionml_tpu.observability.trace import current_request_id, current_trace
from unionml_tpu.observability.slo import SLOConfig, SLOTracker, TenantSLORegistry
from unionml_tpu.observability.timeseries import EngineTimeseries
from unionml_tpu.serving.aot import AOTFunction, resolve_store
from unionml_tpu.serving.metrics import LatencyWindow
from unionml_tpu.serving.overload import (
    DeadlineExceeded,
    QueueFullError,
    TenantThrottled,
    expired,
)
from unionml_tpu.serving.prefix_cache import RadixPrefixCache
from unionml_tpu.serving.tenancy import (
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    active_registry,
    current_priority,
    current_tenant,
    priority_name,
)
from unionml_tpu.models.layers import SlotPlane
from unionml_tpu.models.generate import (
    Generator,
    PrefixCache,
    chunk_aligned,
    gather_paged_rows,
    cache_layouts,
    has_slot_planes,
    init_cache,
    init_paged_cache,
    paste_prefix_rows,
)

__all__ = ["ContinuousBatcher"]

_SENTINEL = object()

#: positions a KV block holds unless the deployment sizes it: the size every
#: benchmark cell runs and ``ops/paged_attention.py`` ``_pages_per_block`` was
#: measured at
KV_BLOCK_SIZE = 64


def _plane(layer: Any) -> Any:
    """One of a cache layer's planes (not its table): the shape oracle of the
    programs that move rows and pages, whatever the model's layout names."""
    return next(buf for name, buf in layer.items() if name != "table")


def _slot_layers(pool_cache: Any) -> "Tuple[bool, ...]":
    """Which of a pool's layers keep a row a slot (a recurrent state, a convolution's
    tail: ``[slots, *shape]`` planes) and not pages: those with no block table
    (:func:`~unionml_tpu.models.generate.init_paged_cache`)."""
    return tuple("table" not in layer for layer in pool_cache)


def _first_paged(cache: Any, slot_layers: "Tuple[bool, ...]") -> Any:
    """The first layer of ``cache`` (a pool, a row cache, a run of pages) that is paged by position."""
    return next(layer for layer, is_slot in zip(cache, slot_layers) if not is_slot)


def _tev(session: "_Session", name: str, **attrs: Any) -> None:
    """Record an event on a session's request trace, if it carries one — the
    single instrumentation shape every engine-side site uses (one ``is not
    None`` test when tracing is off)."""
    trace = session.trace
    if trace is not None:
        trace.event(name, **attrs)


def _refund_admission(registry: "Optional[Any]", tenant: "Optional[str]") -> None:
    """Credit back a :meth:`TenantRegistry.try_admit` charge on a submit path
    that failed after admission — the request was paid for but never served.
    No-op when tenancy is off (tpu-lint TPU017 recognizes refund helpers by
    name, so the None guard can live here without hiding the refund)."""
    if registry is not None:
        registry.refund(tenant)


@dataclasses.dataclass
class _Session:
    """Host-side state of one resident request."""

    slot: int
    out: "queue.Queue[Any]"
    max_new: int  # this request's token budget (<= config.max_new_tokens)
    produced: int = 0  # tokens emitted so far (includes the prefill token)
    finished: bool = False
    #: every token emitted so far — a PREEMPTED request resumes by prefilling
    #: (original prompt + echo), which reproduces its greedy continuation
    #: exactly; bounded by max_new ints of host memory
    echo: "List[int]" = dataclasses.field(default_factory=list)
    #: ``produced`` at the start of the current residency: the device-side
    #: out_buf/produced counters restart at each (re)admission, so host slices
    #: of device output are offset by this base (speculative mode)
    resident_base: int = 0
    #: admission sequence number — preemption evicts the YOUNGEST resident
    admit_seq: int = 0
    #: absolute position of this residency's first decode write
    #: (prefix + resumed-prompt length); drives lazy block growth
    row_start: int = 0
    #: the ORIGINAL prompt from submit(); a resume prefills prompt + echo
    prompt: "List[int]" = dataclasses.field(default_factory=list)
    #: grammar id into the generator's ConstraintSet (0 = FREE); the request's
    #: DFA state is a pure function of (grammar, echo), so preemption resume
    #: recovers it by a host-side walk over the emitted tokens
    grammar: int = 0
    #: absolute ``time.monotonic()`` deadline; a session still WAITING past it
    #: is shed (DeadlineExceeded) instead of occupying the FIFO — work a client
    #: has given up on must never cost a prefill
    deadline: Optional[float] = None
    #: ``time.monotonic()`` at submit(); TTFT = first-token enqueue minus this
    created_at: float = 0.0
    #: ``time.monotonic()`` of the last token emission to this stream; the gap
    #: between consecutive emissions is the TBT series — the stall a streaming
    #: client feels while another prompt's prefill occupies the engine
    last_emit: Optional[float] = None
    #: the submitting request's :class:`~unionml_tpu.observability.trace.RequestTrace`
    #: (None when tracing is off — every engine-side instrumentation site is a
    #: single ``is not None`` test, the strictly-zero-cost-off contract)
    trace: Any = None
    #: leading block-table entries that are SHARED (tree- or prefix-owned,
    #: read-only to this stream): the admission's page write diverts them to
    #: scratch. Without the radix cache this is the static shared-prefix count
    #: — identical numbers to the historical behavior.
    shared_blocks: int = 0
    #: block-table entries currently assigned (shared + private, in table
    #: order); lazy growth appends from here. Ownership of an entry's block can
    #: move to the radix tree without changing the table, so this — not
    #: ``len(_slot_blocks[slot])`` — is the growth cursor.
    table_len: int = 0
    #: radix-tree block ids this session holds pinned (refcounted against
    #: eviction while its table references them); released on
    #: finish/cancel/preempt via ``_release_blocks_locked``
    pins: "List[int]" = dataclasses.field(default_factory=list)
    #: the ACTUAL block ids behind the first ``table_len`` table entries, in
    #: table order — the decode-side radix publish needs the
    #: ids covering the finished stream's prompt + generated tokens, which
    #: ``_slot_blocks`` alone cannot reconstruct once ownership of prompt
    #: blocks moved to the tree
    table: "List[int]" = dataclasses.field(default_factory=list)
    #: disaggregated serving (docs/serving.md): an EXPORT session runs its
    #: prefill here but never takes residency — at admission-complete the
    #: first token is emitted and the prefilled row is packaged as ``handoff``
    #: for a decode-role replica to import
    export: bool = False
    #: the export payload (set just before the sentinel); the replica layer's
    #: relay reads it off the finished stream and imports it elsewhere
    handoff: "Optional[Dict[str, Any]]" = None
    #: an IMPORT session's inbound payload (a sibling replica's export): the
    #: admission skips prefill entirely — the row is placed onto this engine's
    #: submesh and written, whole pages, into freshly allocated blocks
    pending_import: "Optional[Dict[str, Any]]" = None
    #: multi-tenant QoS (serving/tenancy.py): the submitting request's tenant
    #: id (None = anonymous) and priority tier — the deficit-round-robin
    #: admission and priority preemption key on these; all-default values
    #: keep the engine on its historical FIFO path exactly
    tenant: Optional[str] = None
    priority: int = PRIORITY_NORMAL
    #: OpenAI ``logprobs`` support: when True the engine appends each emitted
    #: token's log-probability (from the decode scan's ride-along output) to
    #: ``lp`` BEFORE enqueueing the tokens, so a consumer that has read k
    #: tokens can always read k logprobs off the stream. Off (the default)
    #: costs nothing — the scan computes the column either way, the engine
    #: just doesn't copy it host-side.
    want_logprobs: bool = False
    lp: "List[float]" = dataclasses.field(default_factory=list)
    #: the engine's life-cycle record (observability/engine_log.py), written
    #: once when the request ends, tracing on or off: the submitting request's
    #: id, the prompt's length and the part of it the radix cache served, the
    #: ``time.monotonic()`` of the first admission start and of the first
    #: token (``created_at`` is the submit stamp), and the index of the engine
    #: iteration that emitted that token
    request_id: Optional[str] = None
    prompt_tokens: int = 0
    cached_tokens: int = 0
    admission_started: Optional[float] = None
    first_token_at: Optional[float] = None
    first_iteration: Optional[int] = None


@dataclasses.dataclass(eq=False)  # identity semantics: fields hold device arrays
class _Admission:
    """One in-flight admission: a slot-holding prompt whose prefill may be
    partially complete. With ``admit_chunk`` set, the engine steps these one
    chunk at a time between decode dispatches; without it (or on the
    sequence-parallel / exact-width-overflow paths) the whole prefill runs as
    a single step and the admission never persists across iterations."""

    session: _Session
    prompt: "List[int]"
    slot: int
    seed: int
    budget: int  # this request's remaining generation budget
    blocks_row: np.ndarray  # the slot's block table row, scratch-padded
    started_at: float
    # chunked-prefill progress (populated by _admission_begin)
    chunk: int = 0  # 0 = monolithic (single-step) admission
    width: int = 0  # chunk-aligned prefill width
    pos: int = 0  # next column to prefill
    start: int = 0  # absolute offset of column 0 (the shared prefix length)
    tokens: Optional[np.ndarray] = None  # [1, width] padded prompt
    lengths: Any = None  # device [1] absolute sequence length
    key: Any = None
    row_valid: Any = None
    cstate: tuple = ()
    dfa_state: Optional[int] = None
    row_cache: Any = None  # target model's [1, cache_len] row (filling up)
    last: Any = None  # accumulated last-real-token hidden state
    d_row_cache: Any = None  # draft model's row, chunked in lockstep
    d_last: Any = None  # the draft's: its chunk program (the target's, one for both) takes and donates it
    #: what a counting model counted over each chunk (device arrays, gen.counter_names)
    counts: list = dataclasses.field(default_factory=list)
    # radix prefix cache (prefix_cache=True engines): tokens of the logical
    # sequence already cached (> prefix length on a hit) and the matched block
    # ids, scratch-padded, that the dense-row gather reads
    cached: int = 0
    gather_row: Optional[np.ndarray] = None
    # handoff import: the payload's KV pages in pool layout, placed on this
    # engine's submesh — finalize hands them to the paste's own page write,
    # with no row to lay as pages first
    import_pages: Optional[tuple] = None
    # completion products consumed by _finalize_admission
    tok0: Any = None
    row_len: Any = None
    done: bool = False


class _TokenStream:
    """The iterator :meth:`ContinuousBatcher.submit` returns.

    A plain class rather than a generator on purpose: generator ``close()``
    cannot reach a request abandoned before its first ``next()`` (the body
    never ran) and raises "already executing" against one blocked mid-``next``
    — this ``close`` is callable from any thread at any time and cancels the
    session directly. Dropping the last reference also cancels (``__del__``),
    so streams abandoned inside wrapping generators are released by refcount.
    """

    def __init__(self, batcher: "ContinuousBatcher", session: _Session):
        self._batcher = batcher
        self._session = session

    def __iter__(self) -> "Iterator[np.ndarray]":
        return self

    def __next__(self) -> np.ndarray:
        item = self._session.out.get()
        if item is _SENTINEL:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self) -> None:
        self._batcher._cancel(self._session)

    @property
    def logprobs(self) -> "List[float]":
        """Log-probabilities of the tokens emitted so far (``submit(...,
        logprobs=True)`` streams only). The engine appends each chunk's
        logprobs BEFORE enqueueing its tokens, so after consuming k tokens at
        least k entries are here — the OpenAI surface slices them chunk by
        chunk."""
        return list(self._session.lp)

    @property
    def handoff(self) -> "Optional[Dict[str, Any]]":
        """The export payload of a ``submit(..., export_handoff=True)`` stream
        once it has finished (None while in flight, or when the stream
        completed outright — eos/budget at the prompt-sampled token, a shed, or
        a cancel). The replica layer imports it into a decode-role replica."""
        return self._session.handoff

    def __del__(self):  # pragma: no cover - refcount backstop
        try:
            self.close()
        except Exception:
            pass


class ContinuousBatcher:
    """Share decode dispatches across concurrent generation requests.

    >>> batcher = ContinuousBatcher(generator, slots=4)
    >>> for chunk in batcher.submit([1, 5, 9]):   # 1-D int32 arrays
    ...     ...
    >>> batcher.close()

    ``slots`` bounds resident concurrency; excess requests wait for a free slot
    (FIFO). ``decode_chunk`` is the scan length per shared dispatch — smaller
    chunks mean lower time-to-next-token and more frequent admission points,
    larger chunks amortize per-dispatch overhead.

    ``admit_chunk`` enables **stall-free admission**: the admission prefill is
    sliced into ``admit_chunk``-token chunks and the engine alternates chunks
    with decode dispatches, running at most ``prefill_budget`` prefill tokens
    per iteration (default: one chunk) with up to ``max_admissions``
    partially-prefilled prompts in flight — resident streams' time-between-
    tokens is bounded by ~one chunk's dispatch instead of one whole prompt,
    and the chunked first token is bit-identical to the monolithic one (the
    chunked-prefill equality contract ``models/generate.py`` already pins).
    Defaults resolve constructor kwarg → ``serve`` CLI/env export →
    ``GenerationConfig.prefill_chunk`` → monolithic admission. ``stats()``
    reports TTFT/TBT percentiles and prefill-chunk counters for ``/metrics``. ``prefix`` (a :class:`~unionml_tpu.models.generate.PrefixCache`
    from ``generator.cache_prefix``) is a server-wide shared prompt prefix — a
    system prompt — whose K/V rows are pasted into every admission, so its
    prefill cost is paid once at ``cache_prefix`` time, not per request; every
    submitted prompt is then a suffix after it.

    The KV cache is PAGED: K/V live in a shared pool of ``pool_blocks`` blocks
    of ``block_size`` positions and each admission is allocated only the
    blocks ITS prompt + budget need — HBM scales with resident tokens, so a
    pool far smaller than ``slots x cache_len`` still admits a full house of
    typical requests (vLLM's insight, expressed in static XLA shapes; no
    reference analog). Both are deployment sizing. ``pool_blocks`` defaults to
    ``slots x max_blocks``, a pool that holds every slot at its worst case:
    such an engine never waits for blocks and never preempts for space. With a
    smaller pool admission blocks FIFO while the pool is exhausted and resumes
    as residents finish, and residents that cannot grow are preempted and
    resumed token-identically; ``stats()["kv_blocks"]`` reports occupancy.
    Decoded tokens are exactly a sequential ``Generator`` run's (the test ring
    pins paged == sequential at one block a row and at several).

    ``prefix_cache=True`` (env default
    ``UNIONML_TPU_PREFIX_CACHE`` / serve ``--prefix-cache``) turns on the
    **radix prefix cache** (serving/prefix_cache.py): completed admissions
    publish their prompts' full KV blocks into a per-engine radix tree, and
    any later prompt extending a cached prefix skips prefill for the cached
    portion — gathered from the shared blocks, chunk-prefilled only from the
    first uncached token. Cached blocks are refcount-pinned while a resident
    references them, copied-on-write when a request diverges inside a shared
    tail block, and LRU-evicted back into the allocator under pool pressure
    (admission never deadlocks against a full cache). Cached-prefix output is
    bit-identical to a cold prefill; with the flag off nothing is published or
    matched and ``stats()`` has no ``prefix_cache`` section.
    ``stats()["prefix_cache"]`` carries hit/miss/eviction/CoW counters and
    ``tokens_avoided``.

    ``slo`` arms the **fleet health & SLO engine** (observability/{timeseries,
    slo,health}.py, docs/observability.md "SLOs and fleet health"): windowed
    rates fed per iteration, declarative latency/shed targets evaluated with
    multi-window burn rates, per-request breach exemplars, and a cached
    ``health()`` score the replica scheduler routes on. ``None`` (default)
    reads the ``serve --slo-*`` env exports, an
    :class:`~unionml_tpu.observability.slo.SLOConfig` overrides them, and
    ``False`` disables the layer entirely (no windowed telemetry, no SLO
    tracking). ``stats()`` gains ``rates`` (and ``slo`` when targets are armed).

    ``role`` (disaggregated serving, docs/serving.md "Disaggregated and
    elastic serving") tags the engine ``prefill``/``decode``/``mixed`` for the
    replica layer and unlocks the KV handoff pair:
    ``submit(..., export_handoff=True)`` runs ONLY the prefill here — the
    stream emits the prompt-sampled token, ends, and carries the prompt's KV
    pages on its ``handoff`` attribute — and :meth:`import_handoff` on a
    sibling engine adopts those pages into freshly allocated blocks without
    re-running any prefill. Output across the pair is bit-identical to a
    single mixed engine serving the same request. With ``None`` (the default)
    ``stats()`` carries no role or handoff section.
    """

    def __new__(cls, generator: Optional[Generator] = None, **engine_kwargs: Any):
        """Replica delegation: constructing the engine over a mesh with a >1
        batch axis (``data``/``fsdp``/``dcn_data``), or with the serve CLI's
        ``--dp-replicas`` exported, transparently returns a
        :class:`~unionml_tpu.serving.replicas.ReplicaSet` — N per-submesh
        engines behind a least-loaded scheduler with the same public surface
        (every ``__init__`` knob applies per replica). A batch-1 admission row
        cannot split a batch axis, so the batch extent IS the replica count;
        apps opt into replica serving by mesh shape or CLI flag with no code
        changes."""
        if cls is ContinuousBatcher and generator is not None:
            mesh = getattr(generator, "mesh", None)
            dp = 1
            if mesh is not None:
                for axis in ("dcn_data", "data", "fsdp"):
                    dp *= int(mesh.shape.get(axis, 1))
            env = serve_dp_replicas()
            # a role spec implies its own fleet size (prefill=1,decode=3 is a
            # 4-replica fleet) — `serve --replica-roles` alone must replicate,
            # exactly like --dp-replicas; an explicit roles= kwarg does too
            roles_kw = engine_kwargs.get("roles")
            if isinstance(roles_kw, dict):
                role_total = sum(roles_kw.values())
            elif isinstance(roles_kw, (list, tuple)):
                role_total = len(roles_kw)
            else:
                role_total = sum(serve_replica_roles().values())
            if dp > 1 or env > 1 or role_total > 1:
                from unionml_tpu.serving.replicas import ReplicaSet

                return ReplicaSet.from_generator(
                    generator, replicas=env or (role_total or None), **engine_kwargs
                )
        return super().__new__(cls)

    @classmethod
    def _single(cls, generator: Generator, **kwargs: Any) -> "ContinuousBatcher":
        """Build one plain engine, bypassing the ``__new__`` replica
        delegation — the replica layer constructs its per-submesh engines
        through this (each submesh has batch extent 1, but the ``--dp-replicas``
        env check must not recurse)."""
        self = object.__new__(cls)
        self.__init__(generator, **kwargs)
        return self

    def __init__(
        self,
        generator: Generator,
        *,
        slots: int = 4,
        decode_chunk: int = 8,
        prefix: Optional[PrefixCache] = None,
        block_size: int = KV_BLOCK_SIZE,
        pool_blocks: Optional[int] = None,
        max_waiting: Optional[int] = None,
        admit_chunk: Optional[int] = None,
        prefill_budget: Optional[int] = None,
        max_admissions: Optional[int] = None,
        trace: Optional[bool] = None,
        prefix_cache: Optional[bool] = None,
        slo: Optional[Any] = None,
        role: Optional[str] = None,
        tenancy: Optional[Any] = None,
        aot: Optional[Any] = None,
    ):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if role is not None and role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be one of 'prefill'/'decode'/'mixed' (or None), got {role!r}"
            )
        if decode_chunk < 1:
            raise ValueError("decode_chunk must be >= 1")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if max_waiting is not None and max_waiting < 1:
            raise ValueError("max_waiting must be >= 1")
        if admit_chunk is not None and admit_chunk < 0:
            raise ValueError("admit_chunk must be >= 0 (0 = monolithic admission)")
        if prefill_budget is not None and prefill_budget < 0:
            raise ValueError("prefill_budget must be >= 0 (0 = one chunk per iteration)")
        if max_admissions is not None and max_admissions < 0:
            raise ValueError("max_admissions must be >= 0 (0 = default of 1)")
        #: admission bound AHEAD of the slot pool: prompts waiting for a free
        #: slot beyond this are shed at submit() with QueueFullError (HTTP 429)
        #: instead of growing _pending without bound under overload
        self.max_waiting = SERVE_MAX_WAITING if max_waiting is None else max_waiting
        #: request-timeline annotation: the engine records lifecycle events
        #: (admission start, prefill chunks, emissions, finish/shed) onto the
        #: trace each submit() captured from its context. True by default —
        #: the HTTP layer's tracing switch decides whether a trace EXISTS, so
        #: with tracing off every site is one ``is not None`` test; False
        #: opts this engine out entirely (the bench lane's control arm).
        self.trace_requests = True if trace is None else bool(trace)
        cfg = generator.config
        self.gen = generator
        #: AOT program store (serving/aot.py, docs/serving.md "Cold start and
        #: AOT preload"). Resolution mirrors admit_chunk: a ProgramStore or
        #: directory kwarg pins it, None reads the serve CLI's
        #: UNIONML_TPU_AOT_PRELOAD export, False disables. With a store armed,
        #: the generator's prefill/decode programs AND this engine's
        #: admit/gather helpers resolve load-before-compile — warmup() on a
        #: populated store deserializes executables in milliseconds instead of
        #: compiling, and every compile it does pay is serialized back for the
        #: next cold process. Off (the default) keeps the engine byte-for-byte
        #: the plain-jit one, stats() included.
        self._aot = resolve_store(aot)
        if self._aot is not None:
            generator.enable_aot(self._aot)
            # the generator may already carry a store from an earlier engine
            # (or an explicit enable_aot): surface THAT one so telemetry and
            # key context stay consistent with the programs actually wrapped
            self._aot = generator._aot_store
        #: stall-free admission (chunked prefill interleaved with decode).
        #: Resolution mirrors the --dp-replicas pattern: constructor kwarg,
        #: then the serve CLI's env export, then the model's own
        #: ``prefill_chunk`` (a config that already chunks long-context
        #: prefill wants its admissions chunked too); None disables chunking
        #: (monolithic admission, the pre-chunking behavior).
        if admit_chunk is None:
            admit_chunk = serve_admit_chunk() or (cfg.prefill_chunk or 0)
        self.admit_chunk: Optional[int] = int(admit_chunk) or None
        #: prefill tokens per engine iteration between decode dispatches; the
        #: default of one chunk bounds resident TBT at ~one chunk's dispatch
        if prefill_budget is None:
            prefill_budget = serve_prefill_budget()
        self.prefill_budget: Optional[int] = (
            int(prefill_budget) or self.admit_chunk or None
        )
        #: concurrent partially-prefilled admissions; monolithic admissions
        #: complete within one step, so the cap only matters in chunked mode
        if max_admissions is None:
            max_admissions = serve_max_admissions()
        self.max_admissions = max(int(max_admissions), 1) if max_admissions else 1
        #: whether a layer of the model keeps state with no position axis, one row a slot (a
        #: recurrent state): what no position of a pool can give back, so the paths that resume
        #: in the middle of a sequence are refused below, each by name (``config.draft`` already by ``Generator``)
        slot_state = has_slot_planes(generator.module.config)
        #: speculative mode: with ``config.draft`` set, resident rows advance by
        #: draft-and-verify ROUNDS instead of single decode steps — the engine
        #: drives the SpeculativeGenerator's batch round loop (per-row floors
        #: and budgets), so concurrent streams share draft+verify dispatches
        #: and each greedy stream still equals its solo target-only run
        self._spec = generator._speculative() if cfg.draft is not None else None
        if self._spec is not None and self._aot is not None:
            # the draft model's prefill/decode programs preload from the same
            # store (its own context: draft architecture, same mesh)
            self._spec._draft.enable_aot(self._aot)
        #: disaggregated-serving role (informational except for the guards
        #: below; None = a role-less engine whose stats() stay byte-for-byte
        #: the historical ones). The replica scheduler routes long-prompt
        #: admissions to prefill-role engines and hands their finished KV off
        #: to decode-role engines (docs/serving.md "Disaggregated and elastic
        #: serving").
        self.role = role
        if role == "prefill" and self._spec is not None:
            raise ValueError(
                "a prefill-role engine does not compose with speculative decoding "
                "(config.draft) yet: the draft's row cannot ride the KV handoff"
            )
        if prefix is not None and not isinstance(prefix, PrefixCache):
            raise TypeError(f"prefix must be a PrefixCache (from generator.cache_prefix), got {type(prefix).__name__}")
        #: speculative × prefix: the draft model needs the system prompt in ITS
        #: cache too — built once here from the prefix's token ids (paid at
        #: construction, like cache_prefix itself)
        self._draft_prefix = (
            self._spec.draft_prefix(prefix) if self._spec is not None and prefix is not None else None
        )
        self.slots = slots
        self.decode_chunk = decode_chunk
        self.prefix = prefix
        #: room for the shared prefix, every bucketed prompt, the full budget,
        #: plus overshoot: one chunk of decode, or one round's gamma+1 verify
        #: writes in speculative mode (which never runs the plain decode)
        overshoot = (self._spec.gamma + 1) if self._spec is not None else decode_chunk
        self._overshoot = overshoot  # also bounds per-request block needs
        p0 = prefix.length if prefix is not None else 0
        widest = max(cfg.prompt_buckets, default=64)
        self.cache_len = p0 + widest + cfg.max_new_tokens + overshoot
        #: sp admission (sp_prefill + a >1 "sequence" mesh axis, no shared
        #: prefix — the same dispatch rule as Generator._start): each bucket
        #: pads to a sequence-axis multiple so every shard gets equal columns,
        #: and the row cache must hold that aligned width
        self._sp_seq = (
            int(generator.mesh.shape.get("sequence", 1)) if generator.mesh is not None else 1
        )
        if cfg.sp_prefill and self._sp_seq > 1 and prefix is None:
            sp_aligned = max(
                chunk_aligned(b, self._sp_seq) for b in (cfg.prompt_buckets or (widest,))
            )
            self.cache_len = max(self.cache_len, sp_aligned)
        if prefix is not None and cfg.prefill_chunk:
            # the offset chunked prefill pads each bucket to a chunk multiple and
            # writes that full aligned width at [p0, p0+aligned) — with a large
            # prefill_chunk that can reach past the budget-sized tail, so size
            # for the widest aligned bucket too (the same rule
            # Generator._start_with_prefix applies to its own cache_len)
            aligned = max(
                chunk_aligned(b, cfg.prefill_chunk) for b in (cfg.prompt_buckets or (widest,))
            )
            self.cache_len = max(self.cache_len, p0 + aligned)
        if self.admit_chunk:
            # chunked admission pads each bucket to an admit_chunk multiple and
            # writes the full aligned width at [p0, p0 + aligned) — size the
            # row cache for the widest aligned bucket, the same rule the
            # prefix/prefill_chunk paths apply above
            aligned = max(
                chunk_aligned(b, self.admit_chunk) for b in (cfg.prompt_buckets or (widest,))
            )
            self.cache_len = max(self.cache_len, p0 + aligned)
        #: radix prefix cache (automatic cross-request KV reuse over the
        #: pool's blocks, serving/prefix_cache.py). Resolution mirrors
        #: admit_chunk: constructor kwarg, then the serve CLI's
        #: UNIONML_TPU_PREFIX_CACHE export; off is the default.
        enable_radix = serve_prefix_cache() if prefix_cache is None else bool(prefix_cache)
        if enable_radix and slot_state:
            raise ValueError(
                "prefix_cache over a model that keeps a recurrent state a slot: a hit at position p needs "
                "every such layer's state at p, and the pool holds only each slot's newest"
                + ("" if prefix_cache else " (UNIONML_TPU_PREFIX_CACHE switched it on: unset it for this model)")
            )
        if enable_radix:
            if cfg.draft is not None:
                raise ValueError(
                    "prefix_cache does not compose with speculative decoding (config.draft) yet"
                )
            if prefix is not None and prefix.tokens is None:
                raise ValueError(
                    "prefix_cache with a shared prefix needs its token ids (build the "
                    "PrefixCache with generator.cache_prefix) so the prefix joins the radix key"
                )
            #: cache-hit admissions always prefill chunked (the chunk program is
            #: the one compile-bounded prefill for arbitrary start offsets);
            #: chunk resolution adds block_size as the final fallback so the
            #: cache works on engines that never enabled stall-free admission
            self._radix_chunk = self.admit_chunk or (cfg.prefill_chunk or 0) or block_size
            # a hit's suffix is chunk-aligned from an arbitrary (non-aligned)
            # start, which can reach one chunk past the cold path's widest
            # aligned write — size the rows for it
            aligned = max(
                chunk_aligned(b, self._radix_chunk) for b in (cfg.prompt_buckets or (widest,))
            )
            self.cache_len = max(self.cache_len, p0 + aligned + self._radix_chunk)
        else:
            self._radix_chunk = 0
        if generator.mesh is not None:
            # TP (model-axis) serving is supported: params and KV heads shard,
            # XLA inserts the collectives, and admission's batch-1 row prefill
            # replicates trivially. Batch-axis sharding is not: a [1, ...] row
            # cache cannot split over a >1 data/fsdp axis — normal construction
            # delegates such meshes to the replica layer in __new__; this
            # backstop catches subclasses built directly over a dp mesh
            for axis in ("dcn_data", "data", "fsdp"):
                if int(generator.mesh.shape.get(axis, 1)) > 1:
                    raise ValueError(
                        f"a single continuous engine shards over model/TP axes only; mesh has {axis}="
                        f"{int(generator.mesh.shape[axis])} (batch-1 admission prefills cannot split a "
                        "batch axis) — serve a dp mesh through serving.ReplicaSet"
                    )
        #: a host-side allocator hands pool blocks to admissions; block index
        #: ``pool_blocks`` is the SCRATCH block — unused/finished table entries
        #: point there, so their ride-along writes land harmlessly outside every
        #: live allocation
        self.block_size = block_size
        # the pool composes with TP: the heads-major pools shard over the model
        # axis (Generator._place_paged_cache), tables replicate, and
        # admission's page write indexes only an unsharded pool dim (the blocks)
        self.max_blocks = -(-self.cache_len // block_size)
        self.pool_blocks = pool_blocks if pool_blocks is not None else slots * self.max_blocks
        if self.pool_blocks < self.max_blocks:
            raise ValueError(
                f"pool_blocks ({self.pool_blocks}) must cover one worst-case request "
                f"({self.max_blocks} blocks of {block_size}) or admission could deadlock"
            )
        self._scratch_block = self.pool_blocks
        #: bytes one pool block occupies across the target model's layers
        #: at the POOL dtype — int8 pools carry f32 k/v scale planes (4 B
        #: per (position, head) each) next to the 1-byte values, so the
        #: int8-aware byte gauges on /metrics reflect what HBM actually
        #: holds, not a naive values-only halving
        mcfg = generator.module.config
        #: the planes a layer keeps a position in, by name: heads, width, bytes a value
        #: (the model's own layout: keys and values, or a latent layer's one row)
        #: a layer states either those or planes with no position axis, one row a slot
        #: (models/layers.py SlotPlane: a recurrent state): by name, shape and bytes a value
        self._kv_layout, self._slot_layout = {}, {}
        self._block_bytes = self._slot_bytes = 0
        layouts = cache_layouts(mcfg, cfg.kv_cache_dtype)
        for layout in layouts:
            for name, plane in layout.items():
                if isinstance(plane, SlotPlane):
                    self._slot_layout[name] = (tuple(plane.shape), jnp.dtype(plane.dtype).itemsize)
                    self._slot_bytes += int(np.prod(plane.shape)) * jnp.dtype(plane.dtype).itemsize
                else:
                    heads, width, dtype = plane
                    self._kv_layout[name] = (heads, width, jnp.dtype(dtype).itemsize)
                    self._block_bytes += block_size * heads * width * jnp.dtype(dtype).itemsize
        if self._slot_bytes and not self._block_bytes:
            raise ValueError("the engine's pool is pages: a model none of whose layers keeps a position has none")
        #: which layers keep a row a slot, for the one program that cannot ask a pool (the handoff's export); () with none
        self._slot_layers = tuple(isinstance(next(iter(layout.values())), SlotPlane) for layout in layouts) if slot_state else ()
        self._kv_dtype_label = cfg.kv_cache_dtype or str(jnp.dtype(mcfg.dtype))
        self._free_blocks: "List[int]" = list(range(self.pool_blocks))
        self._slot_blocks: Dict[int, "List[int]"] = {}
        #: shared-prefix pages: the system prompt's FULL blocks are written
        #: once and every slot's table points at the same ids — nothing ever
        #: writes positions < p0, so sharing is safe read-only reuse and
        #: each request allocates only blocks past the shared region (its
        #: partial prefix tail, its prompt, its budget). The pool must hold
        #: the shared blocks plus one worst-case request's PRIVATE blocks.
        self._shared_prefix_blocks: "List[int]" = []
        if prefix is not None:
            # (pool >= max_blocks already covers shared + worst-case private)
            n_shared = prefix.length // block_size
            self._shared_prefix_blocks = [self._free_blocks.pop(0) for _ in range(n_shared)]
        #: the radix tree over the pool's blocks; None = prefix caching off
        #: (every radix code path below is gated on this)
        self._radix: Optional[RadixPrefixCache] = None
        if enable_radix:
            self._radix = RadixPrefixCache(block_size)
            if self._shared_prefix_blocks:
                # the static shared prefix is the tree's permanently pinned
                # root run — matches walk through it, and the first admission
                # caches its partial tail block (plus the prompt) on top
                self._radix.insert(
                    list(self.prefix.tokens)[: len(self._shared_prefix_blocks) * block_size],
                    list(self._shared_prefix_blocks),
                )
                self._radix.pin(self._shared_prefix_blocks)
        self._lock = threading.Condition()
        self._pending: "List[tuple]" = []  # (prompt, session) awaiting a free slot
        self._admissions: "List[_Admission]" = []  # slot-holding, prefill in flight
        self._sessions: Dict[int, _Session] = {}
        self._free = list(range(slots))
        self._cancelled: "List[_Session]" = []  # resident sessions whose consumer went away
        self._ended: "List[_Session]" = []  # finished in the emit under way; owed the sentinel (_end_streams_locked)
        self._closed = False
        #: scale-down quiesce (replicas.py): a quiesced engine sheds NEW
        #: submits with QueueFullError — the replica scheduler walks past it —
        #: while its pending queue and residents drain to completion, so a
        #: resize never truncates a stream a stale routing snapshot sent here
        self._quiesced = False
        self._carry: Optional[tuple] = None  # (cache, tok, lengths, done, key)
        #: the host's account of what it writes into the carry between
        #: dispatches (engine thread only): every slot's table row as the device
        #: should hold it, the rows edited since the last sync and the slots
        #: released since. ``_extend_tables`` / ``_mask_slot_done``
        #: write here and ``_sync_carry`` carries the lot to the device in one
        #: dispatch, whatever the number of rows, blocks and layers
        self._table_host = np.full((slots, self.max_blocks), self._scratch_block, np.int32)
        self._edited_host = np.zeros((slots,), bool)
        self._released_host = np.zeros((slots,), bool)
        self._sync_fn = jax.jit(self._sync_impl, donate_argnums=(0, 1, 2))
        self._seed = 0
        self._thread: Optional[threading.Thread] = None
        # donate only the pool-side buffers: the [1, ...] row caches can't alias
        # any output shape, so donating them would just trigger warnings
        self._paged_admit_fn = jax.jit(self._paged_admit_impl, donate_argnums=(0,))
        self._paged_spec_admit_fn = jax.jit(
            self._paged_spec_admit_impl, donate_argnums=(0, 1, 2)
        )
        # block-native handoff (docs/serving.md "Disaggregated and elastic
        # serving"): the export slices the prefilled row into block-sized
        # pages (payload bytes scale with the PROMPT, not cache_len — the
        # cross-host transfer contract) and the import writes them whole into
        # the pool, by the page write the local paste ends in. One compile per
        # distinct page count, each a trivial reshape/scatter; bounded by
        # max_blocks.
        self._export_pages_fn = jax.jit(self._export_pages_impl, static_argnums=(1, 2, 3))
        self._paged_page_admit_fn = jax.jit(self._paged_page_admit_impl, donate_argnums=(0,))
        # a constrained generator's DFA state enters the carry's tail at admission
        def slot_set(arr, slot, value):
            return arr.at[slot].set(value)

        self._slot_set_fn = jax.jit(slot_set, donate_argnums=(0,))

        # a speculative dispatch's per-row floor: every unfinished row gains >= decode_chunk tokens (capped by its
        # budget); free slots are done and ignored
        def spec_floor(produced, budget, chunk):
            return jnp.minimum(produced + chunk, budget)

        self._spec_floor_fn = jax.jit(spec_floor)
        self._build_admission_programs()
        if self._aot is not None:
            # the admission's page writes preload too — on a cold TPU the
            # write into a big pool is its own multi-second compile
            ectx = self.gen._aot_context()
            self._paged_admit_fn = AOTFunction(
                self._paged_admit_fn, "paged_admit", self._aot, ectx
            )
            self._paged_spec_admit_fn = AOTFunction(
                self._paged_spec_admit_fn, "paged_spec_admit", self._aot, ectx
            )
        #: where the engine thread's time goes (observability/engine_log.py):
        #: phase spans on the profiler's clock and the host's, the iteration
        #: ring and the request life-cycle ring — always on, one per engine
        self.engine_log = EngineLog()
        #: dispatch/utilization counters for benchmarks and /metrics
        self.decode_dispatches = 0
        self.decoded_rows = 0
        self.preemptions = 0
        #: stall-free-admission telemetry: chunked prefill dispatches, tokens
        #: prefilled through them, and admissions that ran as one dispatch
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        self.prefill_monolithic = 0
        #: latency reservoirs for /metrics: TTFT (submit -> first token) and
        #: TBT (gap between consecutive emissions to one resident stream)
        self._ttft = LatencyWindow()
        self._tbt = LatencyWindow()
        #: fleet health & SLO engine (observability/{timeseries,slo,health}).
        #: ``slo=`` resolution: an SLOConfig uses it directly; None/True reads
        #: the serve --slo-* env exports (the --dp-replicas contract); False
        #: disables windowed telemetry AND SLO tracking entirely (the bench
        #: lane's control arm — the pre-health-engine engine, byte for byte).
        if slo is False:
            self.timeseries: Optional[EngineTimeseries] = None
            self.slo: Optional[SLOTracker] = None
        else:
            if slo is None or slo is True:
                slo_config = SLOConfig.from_env()
            elif isinstance(slo, SLOConfig):
                slo_config = slo
            else:
                raise TypeError(
                    f"slo must be an SLOConfig, True/None (read the UNIONML_TPU_SLO_* "
                    f"exports) or False (disable), got {type(slo).__name__}"
                )
            self.slo = SLOTracker(slo_config)
            # ring horizon covers the slow burn-rate window so both SLO
            # windows read real history; TTFT/TBT percentiles ride the
            # engine's own (timestamped) reservoirs — one bookkeeping path
            self.timeseries = EngineTimeseries(
                horizon_s=slo_config.slow_window_s, ttft=self._ttft, tbt=self._tbt
            )
        #: PER-TENANT SLO keying (ROADMAP 4(a), docs/observability.md): one
        #: bounded-LRU (timeseries, tracker) pair per tenant whose TenantSpec
        #: arms slo_* targets, fed at the same observation sites as the
        #: engine-level tracker. Empty — and absent from stats() — unless a
        #: registry with armed per-tenant targets sees traffic, so tenancy-off
        #: (and target-less) engines stay byte-for-byte unchanged; slo=False
        #: disables the layer with the rest of the windowed telemetry.
        self._tenant_slo: Optional[TenantSLORegistry] = (
            TenantSLORegistry(self._tenant_slo_config) if self.timeseries is not None else None
        )
        #: lazily-jitted first-token logprob program (logprobs=True submits
        #: only): the decode scan carries logprobs for every DECODED token,
        #: but the prompt-sampled first token needs one extra head+gather over
        #: the admission's accumulated last-hidden row
        self._lp0_fn = None
        #: cached health evaluation (observability/health.engine_health): the
        #: replica scheduler consults health per routing decision, so the full
        #: evaluation (reservoir sorts + SLO state machine) runs at most once
        #: per TTL and submits in between read the cached dict
        self._health_lock = threading.Lock()
        self._health_cache: "Optional[tuple]" = None
        self._health_ttl = 0.5
        #: token-weighted load normalizer: one admit chunk (or one widest
        #: bucket) of queued prefill counts as one unit of scheduling load
        self._load_norm = float(self.admit_chunk or widest)
        #: prefix-cache telemetry (all zero and absent from stats() when the
        #: cache is off): admissions served partly from cache vs not, prompt
        #: tokens whose prefill was skipped, and partially shared tail blocks
        #: copied on write
        self.prefix_cache_hits = 0
        self.prefix_cache_misses = 0
        self.prefix_cache_tokens_avoided = 0
        self.prefix_cache_cow = 0
        #: disaggregated-serving telemetry: prefilled rows exported to a
        #: sibling replica, rows imported from one, and the export→resident
        #: transfer latency (zero/empty — and absent from stats() — on
        #: role-less engines)
        self.handoffs_exported = 0
        self.handoffs_imported = 0
        self._handoff_ms = LatencyWindow()
        #: overload counters: waiting-queue-full sheds and deadline sheds
        self.shed_queue_full = 0
        self.shed_deadline = 0
        #: multi-tenant QoS (serving/tenancy.py, docs/serving.md "Multi-tenant
        #: QoS"). ``tenancy=`` pins a TenantRegistry for this engine (tests,
        #: bespoke embeddings); None consults the process-wide registry the
        #: serving app installs, at submit time — so with no registry AND no
        #: tenant/priority on any waiting request the engine is byte-for-byte
        #: the historical FIFO one (stats() included).
        self._tenancy = tenancy
        #: per-tenant sheds (empty bucket at submit) and admissions that
        #: preempted a lower-priority resident to take its slot
        self.shed_tenant_limit = 0
        self.priority_preemptions = 0
        #: deficit-round-robin state over WAITING tenants: deficits accrue
        #: quantum x weight per round and pay per-prompt token costs; pruned to
        #: the currently waiting tenant set every selection pass, so request-
        #: derived keys cannot grow it beyond max_waiting entries (the TPU009
        #: contract this engine dogfoods)
        self._drr_deficit: "Dict[str, float]" = {}
        self._drr_last: Optional[str] = None
        self._admit_counter = 0
        #: submissions per grammar id (constrained engines): /metrics telemetry
        self._grammar_counts: Dict[int, int] = {}
        # high-water marks of the carry's ride-along counters, so the spec
        # engine's rounds/accepted_tokens telemetry gets per-dispatch deltas
        self._spec_rounds_seen = 0
        self._spec_accepted_seen = 0

    # ------------------------------------------------------------------ device fns

    @staticmethod
    def _export_pages_impl(row_cache, n_blocks, block_size, slot_layers=()):
        """Lay a prefilled ``[1, cache_len, H, last]`` row as its first
        ``n_blocks`` block-sized pages in POOL layout
        (``[H, n_blocks, block_size, last]``): the handoff payload, and the
        first half of the local paste. ``n_blocks``/``block_size`` are static
        (the handoff: one small compile per distinct page count; the paste:
        ``max_blocks``, inside its one program). ``cache_len`` need not be a
        block multiple: where the last page reaches past the row's end it is
        zero-padded (``lengths`` says how many positions are live; the decode
        read masks the rest). A layer ``slot_layers`` (static, a flag a layer;
        none by default) marks keeps a row a slot and no pages: its planes
        ``[1, *shape]`` ride beside the pages as they are."""
        width = n_blocks * block_size
        pages = []
        for index, layer in enumerate(row_cache):
            if index < len(slot_layers) and slot_layers[index]:
                pages.append(dict(layer))
                continue
            page = {}
            for name, buf in layer.items():
                sliced = buf[0, :width]
                sliced = jnp.pad(sliced, ((0, width - sliced.shape[0]), (0, 0), (0, 0)))
                sliced = jnp.swapaxes(sliced, 0, 1)  # [H, width, last]
                page[name] = sliced.reshape(sliced.shape[0], n_blocks, block_size, sliced.shape[-1])
            pages.append(page)
        return tuple(pages)

    @staticmethod
    def _write_pages(cache, pages, ids):
        """The one write into a pool: page ``i`` of every plane
        (``[H, n, block_size, last]``, the pool's own layout) becomes block
        ``ids[i]``, whole. A plane keeps its layout through it (a write of
        ``[H, last]`` slabs a position made XLA re-lay every pool heads-minor
        and back); ids that repeat (the scratch block's) take any one writer.
        A layer with no table holds no page and passes through."""
        return tuple(
            {**layer, **{name: layer[name].at[:, ids].set(page[name].astype(layer[name].dtype)) for name in page}}
            if "table" in layer else layer
            for layer, page in zip(cache, pages)
        )

    @staticmethod
    def _write_slot_rows(cache, pages, slot):
        """The write of the layers that keep a row a slot: each of their planes' one row
        (``[1, *shape]``, riding in ``pages``) becomes row ``slot`` of the pool's, whole —
        whatever the slot's last tenant left there is gone."""
        return tuple(
            layer if "table" in layer else {
                name: jax.lax.dynamic_update_slice(buf, page[name].astype(buf.dtype), (slot,) + (0,) * (buf.ndim - 1))
                for name, buf in layer.items()
            }
            for layer, page in zip(cache, pages)
        )

    @classmethod
    def _paged_page_admit_impl(cls, cache, pages, tok, lengths, done, slot, row_tok, row_len,
                               blocks_row, skip=0):
        """Point slot ``slot``'s table row at ``blocks_row`` in every layer,
        write ``pages`` (pool layout, ``n_blocks <= max_blocks`` of them) WHOLE
        into the first ``n_blocks`` blocks of the row and activate the slot's
        carry entries: the handoff import as it stands (no ``cache_len``-wide
        row is ever materialized on the importing engine), and the second half
        of the local paste. ``blocks_row`` ([max_blocks] int32) is
        scratch-padded past the request's allocation, so pages past it land in
        the scratch block, never in another request's. ``skip`` (traced, so
        per-request cached-run lengths don't multiply compiles) diverts the
        first ``skip`` pages to scratch: those table entries are SHARED pages
        — the static prefix's, or radix-cached runs another request already
        wrote — which already hold exactly the pages' content, so re-writing
        them per admission would be wasted bandwidth (and, for tree-owned
        pages, a data race against their other readers)."""
        slot_layers = _slot_layers(cache)
        n_blocks = _plane(_first_paged(pages, slot_layers)).shape[1]
        scratch = _plane(_first_paged(cache, slot_layers)).shape[1] - 1  # scratch is the last pool block
        ids = jnp.where(jnp.arange(n_blocks) < skip, scratch, blocks_row[:n_blocks])
        new_layers = tuple(
            {**layer, "table": jax.lax.dynamic_update_slice(layer["table"], blocks_row[None], (slot, 0))}
            if "table" in layer else layer
            for layer in cls._write_slot_rows(cls._write_pages(cache, pages, ids), pages, slot)
        )
        tok = jax.lax.dynamic_update_slice(tok, row_tok.astype(tok.dtype), (slot,))
        lengths = jax.lax.dynamic_update_slice(lengths, row_len.astype(lengths.dtype), (slot,))
        done = jax.lax.dynamic_update_slice(done, jnp.zeros((1,), bool), (slot,))
        return new_layers, tok, lengths, done

    @classmethod
    def _paged_admit_impl(cls, cache, row_cache, tok, lengths, done, slot, row_tok, row_len, blocks_row,
                          skip=0):
        """Admission's paste: the prefilled ``[1, cache_len]`` row, laid as
        ``max_blocks`` pages (:meth:`_export_pages_impl`; the last one
        zero-padded where ``cache_len`` is no block multiple), goes through the
        handoff import's own page write (:meth:`_paged_page_admit_impl`: the
        table row, ``skip`` and the carry entries as described there). One
        compile total: ``slot``, ``skip`` and ``blocks_row`` are traced. The
        row's unused tail lands in the scratch block, which ``blocks_row`` is
        padded with; of the last allocated page the positions past the row's
        length hold what the row held there (zeros past ``cache_len``), and
        ``lengths`` masks them. The device trace names this program by this
        function (``perf/layer_metrics/admit_paste_ms.py``)."""
        slot_layers = _slot_layers(cache)
        block_size = _plane(_first_paged(cache, slot_layers)).shape[2]  # pools are heads-major [H_kv, NB, bs, last]
        pages = cls._export_pages_impl(row_cache, blocks_row.shape[0], block_size, slot_layers)
        return cls._paged_page_admit_impl(cache, pages, tok, lengths, done, slot, row_tok, row_len, blocks_row, skip)

    @classmethod
    def _paged_spec_admit_impl(cls, t_cache, d_cache, out_buf, t_row, d_row, tok, lengths, done,
                               produced, slot, row_tok, row_len, row_done, pad, blocks_row, skip=0):
        """Speculative admission: the SAME block ids serve both models —
        their pools are sized in identical block counts (shapes differ), and a
        slot's logical positions are identical in both caches, so one
        allocation drives two page writes."""
        t_cache, tok, lengths, done = cls._paged_admit_impl(
            t_cache, t_row, tok, lengths, done, slot, row_tok, row_len, blocks_row, skip
        )
        d_cache, _, _, _ = cls._paged_admit_impl(
            d_cache, d_row, tok, lengths, done, slot, row_tok, row_len, blocks_row, skip
        )
        out_buf, done, produced = cls._spec_activate(out_buf, done, produced, slot, row_tok, row_done, pad)
        return t_cache, d_cache, out_buf, tok, lengths, done, produced

    @staticmethod
    def _spec_activate(out_buf, done, produced, slot, row_tok, row_done, pad):
        """Speculative activation tail: reset the slot's out_buf row (pad
        everywhere, tok0 at 0), set an explicit start-done flag (a tok0 that is
        already eos, or a budget of 1), and start the produced counter at 1."""
        row = jnp.full((out_buf.shape[1],), pad, out_buf.dtype).at[0].set(row_tok[0])
        out_buf = jax.lax.dynamic_update_slice(out_buf, row[None], (slot, 0))
        done = jax.lax.dynamic_update_slice(done, row_done, (slot,))
        produced = jax.lax.dynamic_update_slice(produced, jnp.ones((1,), produced.dtype), (slot,))
        return out_buf, done, produced

    @staticmethod
    def _sync_impl(tables, lengths, done, table, edited, released):
        """Apply the host's edits to the carry (:meth:`_sync_carry`): every
        layer's table of every cache (``tables``, a tuple per cache of its
        layers' ``[slots, max_blocks]`` tables) takes the ``edited`` rows of
        the host's ``table``, and a ``released`` slot is done. Its length falls
        to 0 as well: a free row's table points at the scratch block, and the
        decode read streams as many positions of it as the row's length says,
        on every step."""
        tables = jax.tree_util.tree_map(lambda t: jnp.where(edited[:, None], table.astype(t.dtype), t), tables)
        return tables, jnp.where(released, 0, lengths), done | released

    def _build_admission_programs(self) -> None:
        """The set-up programs of an admission (its chunks and first token are
        the Generator's own programs, the paste is further down), built once from
        what the engine knows at construction (module configurations,
        ``cache_len``, KV dtype, mesh, speculation): the engine thread then talks
        to the device once per device step of an admission, with host values as
        arguments and no ``jax.numpy`` call of its own.

        - ``_setup_fn(seed, total, prefixes)``: what every admission starts
          from — ``lengths`` ``[1]``, the sampling key (``fold_in(PRNGKey(seed),
          seed)``, bit for bit the eager derivation for every seed under 2**32),
          ``row_valid``, a zeroed last-hidden row and a zeroed ``[1, cache_len]``
          row cache per model (target, then the draft under speculation), the
          shared prefix's rows pasted at ``[0, p0)``. On a mesh the rows come
          out where :meth:`Generator._place_cache` would put them.
        - ``_cached_setup_fn(pool, gather_row, seed, total)``: the same for a
          radix hit, the row gathered from the pool's cached blocks instead."""
        gen, cfg = self.gen, self.gen.config
        #: the models an admission prefills: the target, then the draft under speculation
        self._models = models = (gen,) if self._spec is None else (gen, self._spec._draft)
        cache_len, kv_dtype = self.cache_len, cfg.kv_cache_dtype

        def scalars(seed, total):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), seed)
            lasts = tuple(jnp.zeros((1, g.module.config.dim), jnp.float32) for g in models)
            return jnp.reshape(total, (1,)), key, jnp.ones((1,), bool), lasts

        def rows():
            return tuple(init_cache(g.module.config, 1, cache_len, kv_dtype=kv_dtype) for g in models)

        def admit_setup(seed, total, prefixes):
            # prefixes: one PrefixCache's layers per model, or () without a shared
            # prefix (arguments, not constants: a closed-over array is compiled in)
            made = rows()
            if prefixes:
                made = tuple(paste_prefix_rows(row, pre) for row, pre in zip(made, prefixes))
            return (*scalars(seed, total), made)

        def admit_setup_cached(pool_cache, gather_row, seed, total):
            return (*scalars(seed, total), (gather_paged_rows(pool_cache, gather_row, cache_len),))

        shardings, cached_shardings = None, None
        if gen.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # committed outputs must live where the programs that take them run
            whole = NamedSharding(gen.mesh, PartitionSpec())
            placed = tuple(g._cache_shardings(row) for g, row in zip(models, jax.eval_shape(rows)))
            shardings = (whole, whole, whole, (whole,) * len(models), placed)
            cached_shardings = (whole, whole, whole, (whole,), None)  # the gathered row: as the pool's heads lie
        self._setup_fn = jax.jit(admit_setup, out_shardings=shardings)
        self._cached_setup_fn = jax.jit(admit_setup_cached, out_shardings=cached_shardings)
        if self._aot is not None:
            # what the two programs close over and no argument's shape shows is part
            # of their key: the row's length (constructor knobs raise it: decode_chunk,
            # admit_chunk, prefix_cache), and the draft whose row the set-up also builds
            # (the KV dtype and the target's module are in the Generator's context)
            ectx = {
                **gen._aot_context(),
                "cache_len": cache_len,
                "row_modules": [repr(g.module.config) for g in models],
            }
            self._setup_fn = AOTFunction(self._setup_fn, "admit_setup", self._aot, ectx)
            self._cached_setup_fn = AOTFunction(self._cached_setup_fn, "admit_setup_cached", self._aot, ectx)
        #: the shared prefix's rows per model, the set-up program's third argument
        self._setup_prefixes = tuple(
            pre.layers for pre in (self.prefix, self._draft_prefix)[: len(models)] if pre is not None
        )

    def _issue(self, name: str, fn: Any, *args: Any, **kwargs: Any) -> Any:
        """Hand the runtime one program or transfer (engine thread): the only
        place the engine thread calls a jitted program. ``name`` is the
        program's XLA module name, as a device trace prints it; the engine log
        tallies the iteration's dispatches by it (``dispatched``; those inside
        ``admit`` are ``admit_dispatches``) and asks what ``fn`` returns whether
        the device still has work (``starved_s``)."""
        return self.engine_log.dispatch(name, fn, *args, **kwargs)

    def _admission_setup(self, seed: int, total: int) -> tuple:
        """A cold admission's starting state, one dispatch:
        ``(lengths, key, row_valid, lasts, rows)``, the last two per model."""
        return self._issue("admit_setup", self._setup_fn, np.uint32(seed), np.int32(total), self._setup_prefixes)

    def _seed_shared_prefix(self, cache: Any, prefix_layers: Any) -> Any:
        """Write the prefix's FULL blocks into a pool once, as whole pages;
        every admission's table then points at these ids and nothing ever
        writes them again (decode writes start at ``lengths >= p0``)."""
        ids = jnp.asarray(self._shared_prefix_blocks, jnp.int32)
        n_blocks, block_size = len(self._shared_prefix_blocks), self.block_size

        def seed(cache, prefix_layers, ids):  # prefix rows [1, p0, H, last], p0 >= n_blocks * block_size
            return self._write_pages(cache, self._export_pages_impl(prefix_layers, n_blocks, block_size), ids)

        return jax.jit(seed, donate_argnums=(0,))(cache, prefix_layers, ids)

    def _init_carry(self) -> tuple:
        cfg = self.gen.config
        # pool_blocks + 1: the extra block is scratch (see __init__); tables
        # start all-scratch so never-admitted slots' ride-along writes are
        # harmless from the first dispatch
        cache = self.gen._place_paged_cache(
            init_paged_cache(
                self.gen.module.config, self.slots, self.pool_blocks + 1, self.block_size,
                self.max_blocks, kv_dtype=cfg.kv_cache_dtype, fill_block=self._scratch_block,
            )
        )
        if self._shared_prefix_blocks:
            cache = self._seed_shared_prefix(cache, self.prefix.layers)
        tok = jnp.zeros((self.slots,), jnp.int32)
        # a free row has length 0, before its first admission as after a
        # release (_sync_impl): the decode read streams one block of scratch for it
        lengths = jnp.zeros((self.slots,), jnp.int32)
        done = jnp.ones((self.slots,), bool)  # every slot starts free (= masked out)
        # built inside jit so the key's sharding provenance matches the decode
        # outputs it cycles through (an eager key carries SingleDeviceSharding,
        # jit outputs NamedSharding)
        key = jax.jit(jax.random.PRNGKey)(self._seed)
        if self._spec is None:
            # the carry's tail, as Generator._finish_prefill builds it: the per-slot
            # DFA state of a constrained generator (free slots sit at FREE's 0), then
            # the last dispatch's counts of a model that counts (gen.counter_names)
            tail = (jnp.zeros((self.slots,), jnp.int32),) if self.gen._cs is not None else ()
            if self.gen.counter_names:
                tail += (jnp.zeros((len(self.gen.counter_names),), jnp.int32),)
            return (cache, tok, lengths, done, key, *tail)
        draft_gen = self._spec._draft
        # the draft's pool has the same BLOCK COUNT (different shapes), so
        # one host allocation addresses both caches
        d_cache = draft_gen._place_paged_cache(
            init_paged_cache(
                draft_gen.module.config, self.slots, self.pool_blocks + 1, self.block_size,
                self.max_blocks, kv_dtype=cfg.kv_cache_dtype, fill_block=self._scratch_block,
            )
        )
        if self._shared_prefix_blocks:
            d_cache = self._seed_shared_prefix(d_cache, self._draft_prefix.layers)
        cap = cfg.max_new_tokens + self._spec.gamma + 1
        out_buf = jnp.full((self.slots, cap), cfg.pad_id, jnp.int32)
        produced = jnp.zeros((self.slots,), jnp.int32)
        # spec-loop state layout (speculative.py): rounds/accepted counters ride
        # along; with constraints the per-slot DFA state is the tail element
        # (same convention as the plain carry — existing indices unchanged)
        st = (jnp.zeros((self.slots,), jnp.int32),) if self.gen._cs is not None else ()
        return (cache, d_cache, tok, lengths, done, produced, out_buf,
                jnp.int32(0), jnp.int32(0), key, *st)

    def _prefill_row(
        self,
        prompt: Sequence[int],
        seed: int,
        budget: Optional[int] = None,
        dfa_state: Optional[int] = None,
        allow_sp: bool = True,
    ):
        """Prefill one prompt at batch 1 into a fresh [1, cache_len] cache using
        the Generator's own jitted machinery — identical numerics and the same
        bounded set of prefill compiles (one per bucket at batch 1). With a
        shared ``prefix``, its rows are pasted at slots [0, p0) and the prompt
        (a suffix) flows through the offset chunked path, exactly like
        ``Generator.__call__(..., prefix=...)``. Under speculation the draft's
        row is prefilled here too: the same prompt through the draft model,
        seeded with the DRAFT's prefix rows (its prompt-sampled token is
        discarded — emission #1 is the target's, exactly as in
        ``SpeculativeGenerator._start_state``; ``dfa_state`` rides along, since
        the draft Generator shares the constraints config). ``budget`` is THIS
        request's remaining token budget (default: the config's) — feasibility
        and the resume-width fallback below depend on it, not on the config
        worst case. The rows, key and scalars come from one
        :meth:`_admission_setup`, as a chunked admission's do, dispatched only
        once every model's width is known to fit.

        Returns ``(tok0, lengths, row_cache, last, d_row_cache)`` — ``last`` is
        the prompt's last-token hidden row (``None`` only on the sequence-parallel
        path, which does not surface it); a ``logprobs=True`` admission reads
        it to price the prompt-sampled token, and ``allow_sp=False`` keeps
        such admissions on the dense prefill (token-identical by the
        sp==dense contract) so the row is always available. ``d_row_cache`` is
        the draft's row (``None`` without speculation)."""
        cfg = self.gen.config
        if budget is None:
            budget = cfg.max_new_tokens
        # draft and target prefixes have the same length (same token ids)
        p0 = self.prefix.length if self.prefix is not None else 0

        def plan(gen: Generator, sp_ok: bool) -> tuple:
            """How ``gen`` prefills this prompt: ``(mode, padded tokens, chunk)``."""
            bucket = gen._bucket(max(len(prompt), 1))
            if p0 + bucket + budget > self.cache_len:
                # a PREEMPTED request resumes as prompt + emitted tokens, which can
                # outgrow every configured bucket while still fitting the cache
                # contiguously (_start_admissions checked that prompt + remaining
                # budget <= cache_len) — prefill at the exact width instead of
                # failing the stream; the extra compile is bounded by preemptions
                # being rare
                bucket = max(len(prompt), 1)
            seq = int(gen.mesh.shape.get("sequence", 1)) if gen.mesh is not None else 1
            mode, chunk, width = "dense", 0, bucket
            if self.prefix is not None:
                chunk = cfg.prefill_chunk or bucket
                # ragged tails would cost one extra prefill compile per bucket remainder
                mode, width = "chunks", chunk_aligned(bucket, chunk)
                if p0 + width > self.cache_len:  # __init__ sizes for every bucket;
                    raise ValueError(  # this guards out-of-set prompt widths
                        f"chunk-aligned prefill width {width} + prefix {p0} exceeds cache_len {self.cache_len}"
                    )
            elif sp_ok and gen.config.sp_prefill and seq > 1 and chunk_aligned(bucket, seq) <= self.cache_len:
                # long-context admission: the batch-1 row prefills SEQUENCE-PARALLEL
                # through the Generator's own ring/ulysses shard_map machinery
                # (columns split over the sequence axis; data/fsdp axes are 1 by the
                # mesh guard above), then the row pastes into the pool exactly like
                # any admission — same numerics, same bounded compile set.
                # When the sequence-aligned width would overflow the cache — a
                # PREEMPTION RESUME's exact-width bucket can outgrow every
                # configured bucket while fitting contiguously — the row stays on
                # the dense prefill instead of failing the stream: dense and sp
                # prefill are token-identical, so the resume stays invisible to
                # the consumer (the contract docs/generation.md states)
                mode, width = "sp", chunk_aligned(bucket, seq)
            tokens = np.full((1, width), cfg.pad_id, np.int32)
            tokens[0, : len(prompt)] = np.asarray(prompt, np.int32)
            return mode, tokens, chunk

        # the draft's last-hidden row is never read: it may always prefill sequence-parallel
        plans = [plan(gen, allow_sp or i > 0) for i, gen in enumerate(self._models)]
        # keyed on the admission's own seed (fold_in(PRNGKey(seed), seed), the
        # set-up program's derivation) so overlapping chunked admissions stay
        # deterministic; the rows come zeroed, prefix-seeded and placed
        lengths, key, row_valid, lasts, rows = self._admission_setup(seed, p0 + max(len(prompt), 1))
        # the request's current DFA state masks the prompt-sampled token, same
        # as Generator._start's cstate tail (batch-1 row here)
        cstate = () if dfa_state is None else (np.asarray([dfa_state], np.int32),)
        filled = []
        for gen, (mode, tokens, chunk), last, row_cache in zip(self._models, plans, lasts, rows):
            if mode == "chunks":
                for c in range(0, tokens.shape[1], chunk):
                    last, row_cache, _ = self._issue(
                        "prefill_chunk", gen._prefill_chunk, gen.params, tokens[:, c : c + chunk], np.int32(p0 + c),
                        lengths, row_cache, row_valid, last,
                    )
                tok0 = self._issue("first_token", gen._first_token, gen.params, last, key, *cstate)
            elif mode == "sp":
                if gen._sp_prefill_fn is None:
                    gen._sp_prefill_fn = gen._build_sp_prefill()
                last = None
                tok0, row_cache, _ = self._issue(
                    "sp_prefill", gen._sp_prefill_fn, gen.params, tokens, lengths, row_cache, key, row_valid, *cstate
                )
            else:
                tok0, row_cache, last = self._issue(
                    "prefill", gen._prefill, gen.params, tokens, lengths, row_cache, key, row_valid, *cstate
                )
            filled.append((tok0, row_cache, last))
        tok0, row_cache, last = filled[0]
        return tok0, lengths, row_cache, last, (filled[1][1] if len(filled) > 1 else None)

    def _table_entries(self, tokens: int) -> int:
        """Block-table entries covering positions ``[0, tokens)``."""
        return -(-tokens // self.block_size)

    def _blocks_for_tokens(self, tokens: int, shared: Optional[int] = None) -> int:
        """Private (non-shared) blocks covering positions ``[0, tokens)``.
        Only real, still-visible positions need real blocks: the prefill
        scatter also writes the prompt bucket's pad columns, but those are
        hidden by the ``slot <= position`` mask until decode overwrites them in
        order, so they can land in the scratch block. Blocks covering the
        ``shared`` leading table entries are excluded — the static prefix
        pages every slot reads, plus (radix mode) this request's matched
        cached runs."""
        if shared is None:
            shared = len(self._shared_prefix_blocks)
        return max(0, self._table_entries(tokens) - shared)

    def _blocks_initial(self, prompt: Sequence[int], budget: int, shared: Optional[int] = None) -> int:
        """Blocks an ADMISSION needs — the same target the first
        :meth:`_ensure_capacity_locked` pass will demand (prompt + one chunk of
        lookahead, capped at the request's remaining budget), so a fresh
        admission is never admit-then-instantly-preempted. Allocation is lazy
        from there: residents grow at chunk boundaries and are preempted LIFO
        when the pool runs dry, so resident HBM tracks tokens actually decoded,
        not reserved budgets (the vLLM scheduling model)."""
        p0 = self.prefix.length if self.prefix is not None else 0
        plen = max(len(prompt), 1)
        tokens = min(
            p0 + plen + self.decode_chunk + self._overshoot,
            p0 + plen + budget - 1 + self._overshoot,
        )
        return self._blocks_for_tokens(tokens, shared)

    def _blocks_lifetime(self, prompt: Sequence[int], budget: int) -> int:
        """Worst-case blocks over a request's whole life (prompt + its budget +
        dispatch overshoot) — the feasibility bound for the oversized check and
        the guarantee that a lone worst-case request always fits."""
        p0 = self.prefix.length if self.prefix is not None else 0
        return self._blocks_for_tokens(p0 + max(len(prompt), 1) + budget + self._overshoot)

    # ------------------------------------------------------------------ public API

    def _registry(self) -> Optional[Any]:
        """The tenancy registry in effect: the engine's pinned one, else the
        process-wide active registry (installed by the serving app); None =
        tenancy off. Resolved per call so a registry installed after engine
        construction — the serve startup order — still applies."""
        return self._tenancy if self._tenancy is not None else active_registry()

    def _tenant_slo_config(self, tenant: str) -> "Optional[SLOConfig]":
        """A tenant's per-tenant SLO targets (None = none armed — the
        TenantSLORegistry never creates state for such a tenant)."""
        registry = self._registry()
        if registry is None:
            return None
        return registry.spec(tenant).slo_config()

    def _tenant_shed(self, tenant: Optional[str]) -> None:
        """Feed a shed into the tenant's SLO timeseries (one None test when
        per-tenant SLOs are off; called at every engine shed site)."""
        if self._tenant_slo is not None and tenant is not None:
            self._tenant_slo.shed(tenant)

    def tenant_slo(self) -> "Dict[str, Any]":
        """Per-tenant SLO verdicts (``{}`` with none tracked) — the section
        ``stats()``/``/metrics`` carry and ``/healthz`` merges fleet-wide."""
        if self._tenant_slo is None:
            return {}
        return self._tenant_slo.evaluate()

    def _first_logprob(self, adm: "_Admission") -> Optional[float]:
        """The prompt-sampled first token's log-probability (logprobs=True
        admissions): one lazily-jitted head+log-softmax gather over the
        admission's accumulated last-hidden row — the same constrained policy
        distribution the token was sampled from, so it matches the decode
        scan's ride-along logprobs exactly."""
        if adm.last is None:
            return None  # no hidden state retained (shouldn't happen: sp is fenced)
        gen = self.gen
        if self._lp0_fn is None:
            compute_dtype = getattr(gen.module.config, "dtype", jnp.bfloat16)

            def impl(p, last, tok, *cstate):
                p = gen._dequant_params(p)
                logits = gen._constrain(gen._head_fn(p, last.astype(compute_dtype)), cstate)
                return jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1), tok[:, None], axis=1
                )[:, 0]

            self._lp0_fn = jax.jit(impl)
        lp0 = self._issue("impl", self._lp0_fn, gen.params, adm.last, adm.tok0, *adm.cstate)
        (lp0,) = self.engine_log.fetch("first_logprob", lp0)
        return float(lp0[0])

    def submit(
        self, prompt: Sequence[int], *, max_new_tokens: Optional[int] = None,
        constraint: Optional[int] = None, deadline: Optional[float] = None,
        export_handoff: bool = False, tenant: Optional[str] = None,
        priority: Optional[int] = None, logprobs: bool = False,
    ) -> Iterator[np.ndarray]:
        """Enqueue a prompt; returns an iterator of 1-D int32 arrays of new
        tokens (first item is the prompt-sampled token). Blocks-free: the
        iterator blocks its consumer, not the engine. Safe from any thread.
        ``max_new_tokens`` caps THIS request below the config budget (the cache
        is sized for the config's budget, so larger values are rejected).
        ``constraint`` selects THIS request's grammar from the generator's
        ``config.constraints`` (0 = FREE) — per-request structured output with
        zero extra compiles, since a grammar is just a start state in the
        set's shared table (models/structured.py). ``deadline`` (absolute
        ``time.monotonic()``) sheds the request if it is still WAITING for a
        slot past that instant; when the waiting queue already holds
        ``max_waiting`` live requests, submit sheds immediately with
        :class:`QueueFullError` (HTTP 429) instead of queueing unboundedly.

        ``export_handoff`` (disaggregated serving, the prefill-role path) runs
        ONLY the prefill here: the prompt-sampled first token is emitted and
        the stream then ends with the prefilled KV row packaged on the
        stream's ``handoff`` attribute for :meth:`import_handoff` on a decode
        replica — this engine never spends a decode slot on the request.

        ``tenant``/``priority`` (multi-tenant QoS, docs/serving.md) default to
        the request contextvars the HTTP layer binds: a tenant with an empty
        token bucket is shed with :class:`TenantThrottled` (HTTP 429 whose
        ``Retry-After`` is the bucket's actual refill time), waiting prompts
        are admitted deficit-round-robin across tenants within strict priority
        tiers, and a high-priority admission on a full engine preempts
        the lowest-priority resident (which resumes token-identically)."""
        if len(prompt) == 0:
            raise ValueError("prompt must be non-empty")
        if export_handoff and self._spec is not None:
            raise ValueError(
                "export_handoff does not compose with speculative decoding (config.draft)"
            )
        if logprobs and self._spec is not None:
            raise ValueError(
                "logprobs does not compose with speculative decoding (config.draft) yet: "
                "accepted draft tokens carry no per-token policy logprob"
            )
        if logprobs and export_handoff:
            raise ValueError(
                "logprobs does not compose with export_handoff: the logprob column "
                "does not ride the KV handoff payload (the replica layer routes "
                "logprobs requests onto a decode/mixed replica directly)"
            )
        req_trace = current_trace() if self.trace_requests else None
        if expired(deadline):
            # under the lock: submit runs on arbitrary executor threads, and the
            # engine thread bumps this same counter (lost update otherwise)
            with self._lock:
                self.shed_deadline += 1
                if self.timeseries is not None:
                    self.timeseries.sheds.add()
            self._tenant_shed(tenant if tenant is not None else current_tenant())
            if req_trace is not None:
                req_trace.event("engine.shed_deadline", phase="submit")
            raise DeadlineExceeded("deadline expired before the prompt was enqueued")
        budget = self.gen.config.max_new_tokens
        if max_new_tokens is not None:
            if not (1 <= max_new_tokens <= budget):
                raise ValueError(
                    f"max_new_tokens must be in [1, {budget}] (the config budget the cache is sized for)"
                )
            budget = max_new_tokens
        grammar = 0
        if constraint is not None:
            if self.gen._cs is None:
                raise ValueError("constraint= requires GenerationConfig.constraints on the Generator")
            self.gen._cs.start_states([constraint])  # range check
            grammar = int(constraint)
        # multi-tenant QoS: explicit kwargs win, else the contextvars the HTTP
        # layer bound; priority falls back to the tenant's configured default
        # tier, then normal (the historical behavior — all-default requests
        # keep the engine on its FIFO fast path exactly)
        registry = self._registry()
        if tenant is None:
            tenant = current_tenant()
        if priority is None:
            priority = current_priority()
        if isinstance(priority, str):
            from unionml_tpu.serving.tenancy import parse_priority

            priority = parse_priority(priority)
        if priority is None:
            priority = (
                registry.default_priority(tenant)
                if registry is not None and tenant is not None
                else PRIORITY_NORMAL
            )
        if not (PRIORITY_HIGH <= priority <= 2):
            raise ValueError(f"priority must be in [0, 2] (high/normal/batch), got {priority!r}")
        session = _Session(
            slot=-1, out=queue.Queue(), max_new=budget, grammar=grammar, deadline=deadline,
            created_at=time.monotonic(), trace=req_trace, export=export_handoff,
            tenant=tenant, priority=priority, want_logprobs=bool(logprobs),
            prompt=list(prompt),
            request_id=current_request_id(), prompt_tokens=len(prompt),
        )
        with self._lock:
            if self._quiesced:
                # draining for a scale-down: bounce the request back to the
                # replica scheduler (which walks to a live sibling) without
                # polluting the overload counters — this is routing, not load.
                # Checked before ``_closed``: the resize closes the engine once
                # it reads empty, and a routing snapshot older than that must
                # still walk on, not fail its caller
                raise QueueFullError("replica is quiescing for a fleet resize")
            if self._closed:
                raise RuntimeError("ContinuousBatcher is closed")
            # admission control: count LIVE waiters (cancelled heads awaiting
            # reap don't hold capacity against new arrivals)
            waiting = sum(1 for _, s in self._pending if not s.finished)
            if waiting >= self.max_waiting:
                self.shed_queue_full += 1
                if self.timeseries is not None:
                    self.timeseries.sheds.add()
                self._tenant_shed(tenant)
                if req_trace is not None:
                    req_trace.event("engine.shed_queue_full", waiting=waiting)
                raise QueueFullError(
                    f"continuous-batching waiting queue full ({self.max_waiting} prompts queued "
                    f"ahead of {self.slots} slots)"
                )
            if registry is not None:
                # AFTER the capacity checks, so a full-queue shed never charges
                # the bucket (a replica-walk retry lands on a sibling sharing
                # this registry); a failed try_admit leaves the buckets
                # untouched, so the walk is not double-charged either
                retry_after = registry.try_admit(tenant)
                if retry_after is not None:
                    self.shed_tenant_limit += 1
                    if self.timeseries is not None:
                        self.timeseries.sheds.add()
                    self._tenant_shed(tenant)
                    if req_trace is not None:
                        req_trace.event(
                            "engine.shed_tenant_limit", tenant=tenant,
                            retry_after_s=round(retry_after, 3),
                        )
                    raise TenantThrottled(
                        f"tenant {tenant!r} is over its rate limit",
                        retry_after_s=round(retry_after, 3), tenant=tenant,
                    )
            try:
                if self.gen._cs is not None:
                    self._grammar_counts[grammar] = self._grammar_counts.get(grammar, 0) + 1
                self._pending.append((list(prompt), session))
                if self._thread is None:
                    self._thread = threading.Thread(target=self._engine_loop, daemon=True)
                    self._thread.start()
                self._lock.notify_all()
            except BaseException:
                # the tenant paid for a request that will never be served:
                # undo the charge before propagating, or submit-time failures
                # silently erode the tenant's rate below its configured floor
                _refund_admission(registry, tenant)
                raise
        try:
            if req_trace is not None:
                req_trace.event(
                    "engine.submit", prompt_tokens=len(prompt), queued_behind=waiting,
                    **({"tenant": tenant, "priority": priority_name(priority)} if tenant is not None or priority != PRIORITY_NORMAL else {}),
                )
            return _TokenStream(self, session)
        except BaseException:
            _refund_admission(registry, tenant)
            raise

    def import_handoff(self, payload: Dict[str, Any]) -> Iterator[np.ndarray]:
        """Adopt a sibling replica's exported prefill (disaggregated serving,
        the decode-role path): the payload's KV pages are ``device_put``
        onto this engine's submesh and scattered into freshly allocated blocks
        at admission time — no prefill runs here, so the import costs one
        paste dispatch. The returned stream carries every token AFTER the
        prompt-sampled one (which the exporting replica already emitted); the
        next sampled token is bit-identical to the one a no-handoff run on a
        single mixed replica would produce, because the handed-off KV is
        bit-identical to what this engine's own prefill would have written.

        Imports bypass ``max_waiting``: the prefill cost is already paid and
        the volume is bounded by the exporting replicas' slot pools, so
        shedding here would waste finished work."""
        trace = payload.get("trace") if self.trace_requests else None
        session = _Session(
            slot=-1,
            out=queue.Queue(),
            max_new=int(payload["max_new"]),
            produced=int(payload["produced"]),
            grammar=int(payload.get("grammar", 0)),
            deadline=payload.get("deadline"),
            created_at=payload.get("created_at", time.monotonic()),
            trace=trace,
            tenant=payload.get("tenant"),
            priority=int(payload.get("priority", PRIORITY_NORMAL)),
            prompt=list(payload["prompt"]),
            echo=list(payload["echo"]),
            request_id=current_request_id(), prompt_tokens=len(payload["prompt"]),
        )
        session.pending_import = dict(payload)
        with self._lock:
            if self._quiesced:  # before ``_closed``, as in :meth:`submit`
                raise QueueFullError("replica is quiescing for a fleet resize")
            if self._closed:
                raise RuntimeError("ContinuousBatcher is closed")
            self._pending.append((list(payload["prompt"]), session))
            if self._thread is None:
                self._thread = threading.Thread(target=self._engine_loop, daemon=True)
                self._thread.start()
            self._lock.notify_all()
        return _TokenStream(self, session)

    def _record_end(self, session: _Session, outcome: str) -> None:
        """Write a request's life-cycle record into the engine log: called
        once, where the request ends (finish, cancel, shed, export, error),
        from whichever thread ends it."""
        self.engine_log.request(RequestRecord(
            session.request_id, session.created_at, session.admission_started,
            session.first_token_at, time.monotonic(), session.prompt_tokens,
            session.cached_tokens, session.produced, outcome, session.first_iteration,
        ))

    def _first_token_locked(self, session: _Session, now: float) -> None:
        """Stamp a stream's first token EVER (a preemption resume is a later
        residency, not a first token) on the life-cycle record and feed the
        TTFT readers (caller holds the lock)."""
        session.first_token_at = now
        session.first_iteration = self.engine_log.index
        self._ttft.observe(now - session.created_at)
        if self.slo is not None:
            self.slo.note_ttft(session.trace, (now - session.created_at) * 1e3)
        if self._tenant_slo is not None and session.tenant is not None:
            self._tenant_slo.note_ttft(
                session.tenant, session.trace, now - session.created_at
            )
        _tev(
            session, "engine.first_token",
            ttft_ms=round((now - session.created_at) * 1e3, 3),
        )

    def _cancel(self, session: _Session) -> None:
        """Stop producing for a session whose consumer went away. Safe from any
        thread and at any lifecycle point: pending sessions are dequeued here;
        RESIDENT slots are flagged and the engine (sole device-state owner)
        frees + masks them at the next chunk boundary. A sentinel is pushed so
        a reader blocked in ``__next__`` returns promptly."""
        with self._lock:
            if session.finished:
                return
            session.finished = True
            if any(s is session for _, s in self._pending):
                self._pending = [(p, s) for p, s in self._pending if s is not session]
            elif session.slot >= 0 and self._sessions.get(session.slot) is session:
                self._cancelled.append(session)
            _tev(session, "engine.cancel", produced=session.produced)
            self._record_end(session, "cancel")
            session.out.put(_SENTINEL)
            self._lock.notify_all()

    def _apply_cancellations_locked(self) -> None:
        """Engine thread: free and done-mask slots whose consumers disconnected
        (caller holds the lock). Identity-checked against the resident session —
        a slot that meanwhile finished normally and was re-admitted to a new
        request must not have its new tenant evicted by the stale cancel."""
        cancelled, self._cancelled = self._cancelled, []
        for session in cancelled:
            if self._sessions.get(session.slot) is session:
                self._sessions.pop(session.slot)
                self._free.append(session.slot)
                self._release_blocks_locked(session.slot, session)
                self._mask_slot_done(session.slot)

    def warmup(self) -> None:
        """Resolve the admission/prefill/decode programs before traffic
        arrives, so the first real request never pays a cold XLA compile (tens
        of seconds on TPU — the same rationale as CompiledPredictor's startup
        warmup). A bucket-FILLING request runs through each prompt bucket
        (budget 1: admission only — each bucket is its own prefill shape), then
        a short request exercises one decode/round chunk (the decode program is
        bucket-independent). With an AOT store armed (``aot=`` /
        ``UNIONML_TPU_AOT_PRELOAD``) every program resolves
        **load-before-compile**: a populated store makes this whole pass
        deserialize-bound (milliseconds per program) and an empty one compiles
        once and serializes the result for the next cold process. Counters are
        reset afterwards so ``/metrics`` reflects real traffic only (the AOT
        load/compile telemetry deliberately survives the reset — preload work
        IS the warmup story ``stats()["aot"]`` exists to tell)."""
        cfg = self.gen.config
        for bucket in sorted(cfg.prompt_buckets):
            # length == bucket: _bucket() maps shorter prompts to the smallest
            # fitting bucket, which would leave the larger shapes cold
            prompt = [cfg.pad_id + 1] * bucket
            for _ in self.submit(prompt, max_new_tokens=1):
                pass
        if self._radix is not None and cfg.prompt_buckets:
            with self._lock:
                hit = self.prefix_cache_hits > 0
            if not hit:
                # a radix hit's set-up is a program of its own (the row gathered
                # from the pool): the widest probe again finds its own blocks cached
                for _ in self.submit([cfg.pad_id + 1] * max(cfg.prompt_buckets), max_new_tokens=1):
                    pass
        if cfg.max_new_tokens >= 2:
            # an eos-emitting model can finish a junk prompt at admission
            # (start_done) without ever decoding — vary the prompt a few times.
            # TWO decode dispatches are needed: the very first runs on the
            # freshly initialized carry, whose jit signature differs subtly
            # from the steady-state (decode-output) carry and compiles
            # separately; the second covers what real traffic sees.
            vocab = int(getattr(self.gen.module.config, "vocab_size", 2))
            for salt in range(6):
                if self.decode_dispatches >= 2:
                    break
                tok = 1 + (cfg.pad_id + salt) % max(vocab - 1, 1)
                for _ in self.submit([tok], max_new_tokens=2):
                    pass
            if self.decode_dispatches < 2:
                logger.warning(
                    "warmup never reached the steady-state decode program (eos "
                    "at admission for every probe prompt); the first streams "
                    "may pay a compile"
                )
        with self._lock:
            self.decode_dispatches = 0
            self.decoded_rows = 0
            self.prefill_chunks = 0
            self.prefill_chunk_tokens = 0
            self.prefill_monolithic = 0
            if self._radix is not None:
                # drop the junk prefixes the probe prompts cached (and their
                # hit/miss counts): real traffic must start from a clean tree
                self._radix_reset_locked()
            self._ttft.clear()  # warmup probes must not skew the percentiles
            self._tbt.clear()
            self.engine_log.clear()  # nor their compiles the loop's phase totals
            self.handoffs_exported = 0
            self.handoffs_imported = 0
            self._handoff_ms.clear()
            if self.timeseries is not None:
                # probe tokens/admissions must not read as real traffic rates
                self.timeseries.clear()
            if self.slo is not None:
                self.slo.reset()  # a slow compile-paying probe is not a breach
            if self._tenant_slo is not None:
                self._tenant_slo.clear()  # probe traffic is nobody's tenant SLO
            self._grammar_counts.clear()  # warmup probes all ride FREE (id 0)
            if self._spec is not None:
                # the carry's device-side ride-along counters are NOT reset;
                # the high-water marks already equal them, so future deltas
                # accumulate onto the zeroed telemetry correctly
                self._spec.rounds = 0
                self._spec.accepted_tokens = 0
        with self._health_lock:
            self._health_cache = None  # next health() sees post-reset telemetry

    def configure_slo(self, config: "SLOConfig") -> None:
        """Swap this engine's SLO targets at runtime (retuning a live fleet,
        or arming per-replica targets in tests). The tracker restarts at
        all-ok; the next ``health()`` evaluates fresh against the new targets."""
        if not isinstance(config, SLOConfig):
            raise TypeError(f"config must be an SLOConfig, got {type(config).__name__}")
        if self.timeseries is None:
            raise ValueError(
                "this engine was built with slo=False (windowed telemetry disabled); "
                "SLO targets need the timeseries feed"
            )
        with self._health_lock:
            self.slo = SLOTracker(config)
            self._health_cache = None

    def rates(self, window_s: Optional[float] = None) -> Dict[str, Any]:
        """Windowed rates (tok/s, admissions/s, sheds/s, time-decayed TTFT/TBT
        percentiles) plus the live prefill backlog — the per-replica quantity
        ``/healthz`` exposes and an autoscaler acts on. Defaults to the SLO
        fast window. ``{}`` when the engine was built with ``slo=False``."""
        if self.timeseries is None:
            return {}
        if window_s is None:
            window_s = self.slo.config.fast_window_s if self.slo is not None else 60.0
        out = self.timeseries.rates(window_s)
        out["prefill_backlog_tokens"] = self.queued_prefill_tokens()
        return out

    def health(self, *, max_age_s: Optional[float] = None) -> Dict[str, Any]:
        """This engine's health (observability/health.py): SLO state x
        saturation as one score, cached for ``max_age_s`` (default 0.5 s) so
        the replica scheduler can consult it per routing decision without
        paying the full evaluation each time. ``max_age_s=0`` forces a fresh
        evaluation."""
        from unionml_tpu.observability.health import engine_health

        ttl = self._health_ttl if max_age_s is None else max_age_s
        now = time.monotonic()
        with self._health_lock:
            cached = self._health_cache
        if cached is not None and now - cached[0] < ttl:
            return cached[1]
        fresh = engine_health(self)
        with self._health_lock:
            self._health_cache = (now, fresh)
        return fresh

    def occupancy(self) -> "tuple[int, int]":
        """``(resident, live waiting)`` — the cheap gauge pair the replica
        layer polls per routing decision and per ``/metrics`` snapshot.
        In-flight (partially prefilled) admissions count as waiting: they hold
        a slot but have not produced a token yet."""
        with self._lock:
            waiting = sum(1 for _, s in self._pending if not s.finished)
            waiting += sum(1 for a in self._admissions if not a.session.finished)
            return len(self._sessions), waiting

    @staticmethod
    def _admission_backlog(adm: _Admission) -> int:
        """Prefill tokens an in-flight admission still owes: the unchunked
        remainder once stepping started, else the prompt minus its radix-
        cached run (``adm.start`` still holds the static prefix length before
        :meth:`_admission_begin` runs) — a cache hit is backlog the scheduler
        must not route around. An imported handoff owes NO prefill (the row
        arrives finished), so it contributes nothing."""
        if adm.session.pending_import is not None:
            return 0
        if adm.tokens is not None:
            return max(adm.width - adm.pos, 0)
        remaining = max(len(adm.prompt), 1)
        if adm.cached:
            remaining = max(remaining - max(adm.cached - adm.start, 0), 1)
        return remaining

    def queued_prefill_tokens(self) -> int:
        """Prompt tokens standing between arrivals and their first token: live
        waiting prompts plus the un-prefilled remainder of in-flight
        admissions. The token-weighted signal :meth:`load` (and the replica
        scheduler through it) routes on — two replicas with equal waiter
        counts but a 10k-token vs a 10-token backlog are NOT equally loaded."""
        with self._lock:
            backlog = sum(len(p) for p, s in self._pending if not s.finished)
            for adm in self._admissions:
                if not adm.session.finished:
                    backlog += self._admission_backlog(adm)
            return backlog

    def load(self) -> float:
        """Scheduling load: live residents + live waiters (including in-flight
        admissions), plus the prefill backlog in tokens normalized by the
        admission chunk (or the widest prompt bucket) — the dispatches of work
        queued ahead of a new arrival. The replica scheduler routes
        least-loaded-first on this, so mixed prompt lengths route sensibly."""
        resident, waiting = self.occupancy()
        return resident + waiting + self.queued_prefill_tokens() / self._load_norm

    def stats(self) -> Dict[str, Any]:
        """Utilization snapshot for ``/metrics``: resident/waiting streams,
        shared-dispatch counters, and (speculative mode) realized acceptance.

        The engine lock is held ONLY for the counter/queue/pool reads that
        need it; the latency-window percentile sorts, windowed rates, and the
        SLO evaluation all run after release (each is internally
        synchronized) — a scrape-cadence ``/metrics`` poller must never stall
        the engine thread behind reservoir sorting (the same contract as
        ``LatencyWindow.snapshot`` itself)."""
        with self._lock:
            backlog = sum(len(p) for p, s in self._pending if not s.finished)
            for adm in self._admissions:
                if not adm.session.finished:
                    backlog += self._admission_backlog(adm)
            snapshot: Dict[str, Any] = {
                "slots": self.slots,
                "resident": len(self._sessions),
                "waiting": len(self._pending),
                "admitting": len(self._admissions),
                "max_waiting": self.max_waiting,
                "shed_queue_full": self.shed_queue_full,
                "shed_deadline": self.shed_deadline,
                "draining": self._closed,
                "decode_dispatches": self.decode_dispatches,
                # how the decode program reads the cache, recorded when it
                # was traced: "paged_kernel" or "gather" (None: not traced yet)
                "decode_attention_path": self.gen.decode_attention_path,
                "rows_per_dispatch": round(
                    self.decoded_rows / self.decode_dispatches, 3
                ) if self.decode_dispatches else None,
                "speculative": self._spec is not None,
                # where the engine thread's time went, cumulative: iterations,
                # idle seconds and seconds per phase (docs/observability.md
                # "Where the engine's time goes")
                "loop": self.engine_log.totals(),
                # stall-free admission: knob echo + chunk counters + the
                # prefill backlog the token-weighted load() routes on
                "prefill": {
                    "mode": "chunked" if self.admit_chunk else "monolithic",
                    "admit_chunk": self.admit_chunk or 0,
                    "budget": self.prefill_budget or 0,
                    "max_admissions": self.max_admissions,
                    "chunks": self.prefill_chunks,
                    "chunk_tokens": self.prefill_chunk_tokens,
                    "monolithic_admissions": self.prefill_monolithic,
                    "backlog_tokens": backlog,
                },
            }
            # "used" includes the permanently resident shared-prefix pages
            used = self.pool_blocks - len(self._free_blocks)
            snapshot["kv_blocks"] = {
                "total": self.pool_blocks,
                "used": used,
                "shared_prefix": len(self._shared_prefix_blocks),
                "block_size": self.block_size,
                "preemptions": self.preemptions,
                # byte gauges at the POOL dtype (int8 pools include their
                # f32 scale planes) — ints always, never None, so the
                # Prometheus exposition stays clean; the dtype label is a
                # string, which the exposition skips by design
                "block_bytes": self._block_bytes,
                "used_bytes": used * self._block_bytes,
                "kv_dtype": self._kv_dtype_label,
            }
            # what a layer keeps a position in (the model's layout) and what a block of it weighs
            snapshot["kv_layout"] = {
                "planes": {name: {"heads": h, "width": w, "value_bytes": b} for name, (h, w, b) in self._kv_layout.items()},
                "block_bytes": self._block_bytes,
            }
            if self._slot_bytes:
                # the layers that keep a row a slot instead (a recurrent state): what a slot of
                # them weighs whatever its length, beside what a block of the others does
                snapshot["kv_layout"]["slot_planes"] = {
                    name: {"shape": list(shape), "value_bytes": b} for name, (shape, b) in self._slot_layout.items()
                }
                snapshot["kv_layout"]["slot_bytes"] = self._slot_bytes
            if self.prefix is not None:
                # the static prefix's partial tail block is NOT among the
                # seeded shared pages — each admission writes those
                # tokens again, into a private block (the radix cache, when on,
                # caches the tail like any other run); surface the count
                # so a misaligned prefix/block_size choice is visible
                snapshot["kv_blocks"]["shared_prefix_tail_tokens"] = (
                    self.prefix.length - len(self._shared_prefix_blocks) * self.block_size
                )
            if self._radix is not None:
                # radix prefix cache: admission-level hit/miss counters, the
                # prompt tokens whose prefill the cache skipped, and the
                # tree's structural gauges — every value an int, never None
                # (the /metrics no-None-gauge contract)
                snapshot["prefix_cache"] = {
                    "hits": self.prefix_cache_hits,
                    "misses": self.prefix_cache_misses,
                    "tokens_avoided": self.prefix_cache_tokens_avoided,
                    "cow_copies": self.prefix_cache_cow,
                    "evictions": self._radix.evictions,
                    "evicted_blocks": self._radix.evicted_blocks,
                    "cached_blocks": self._radix.cached_blocks(),
                    "cached_tokens": self._radix.cached_tokens(),
                    # bytes the cached blocks pin in HBM at the POOL dtype —
                    # the gauge that shows the int8 cache holding ~2x the
                    # prefixes of a bf16 pool of the same byte size
                    "cached_bytes": self._radix.cached_bytes(self._block_bytes),
                    "pinned_blocks": self._radix.pinned_blocks(),
                    "nodes": self._radix.nodes(),
                }
            if self.gen.counter_names:
                # what the served model counted (ints, zero until it has run), read from
                # the engine log's one record of it (GET /debug/engine: model_counters),
                # under the keys the model declares: a group of its ``counter_views``
                # over all dispatches and, under "decode", over the decode dispatches
                # alone; a name in no group under its own name
                counted = self.engine_log.counted
                for key, names in self.gen.counter_views.items():
                    snapshot[key] = {**counted(names), "decode": counted(names, "decode")}
                grouped = {n for names in self.gen.counter_views.values() for n in names}
                snapshot.update(counted([n for n in self.gen.counter_names if n not in grouped]))
            if self._slot_bytes:
                # beside what the model counted of its recurrent state (its "state" view, where it has one)
                snapshot.setdefault("state", {}).update(
                    slot_bytes=self._slot_bytes, state_bytes_live=len(self._sessions) * self._slot_bytes
                )
            if self.role is not None:
                snapshot["role"] = self.role
            if self.role is not None or self.handoffs_exported or self.handoffs_imported:
                # disaggregated serving: the engine's role plus its handoff
                # counters (ints only; the transfer-latency window rides the
                # post-lock section below) — absent on role-less engines that
                # never handed off, so their stats stay byte-for-byte the
                # historical ones. A ROLE-LESS engine can still export/import:
                # the cluster coordinator disaggregates at HOST granularity
                # over mixed per-host fleets (serving/cluster.py)
                snapshot["handoff"] = {
                    "exported": self.handoffs_exported,
                    "imported": self.handoffs_imported,
                }
            if (
                self._registry() is not None
                or self.shed_tenant_limit
                or self.priority_preemptions
            ):
                # multi-tenant QoS: per-engine counters (per-tenant detail —
                # buckets, admitted/shed/generated — lives on the registry's
                # own stats, surfaced by the app's /metrics snapshot); absent
                # entirely when QoS is off, the byte-for-byte contract
                snapshot["tenancy"] = {
                    "shed_tenant_limit": self.shed_tenant_limit,
                    "priority_preemptions": self.priority_preemptions,
                }
            if self._spec is not None and self._spec.rounds:
                snapshot["acceptance_rate"] = round(
                    self._spec.accepted_tokens / (self._spec.rounds * self._spec.gamma), 3
                )
            if self.gen._cs is not None:
                # structured-output adoption: how many submissions rode each
                # grammar (0 = FREE) — the signal for sizing the ConstraintSet
                snapshot["grammar_submissions"] = dict(sorted(self._grammar_counts.items()))
        # ---- window work, OUTSIDE the engine lock (each structure below is
        # internally synchronized; sorting reservoirs here must not stall the
        # engine thread behind a scrape)
        # first-token and between-token latency percentiles (ms); an empty
        # window reports {"window": 0}, never a None gauge
        snapshot["ttft_ms"] = self._ttft.snapshot()
        snapshot["tbt_ms"] = self._tbt.snapshot()
        if self._aot is not None:
            # AOT preload telemetry (internally synchronized; absent entirely
            # with the store off — the byte-for-byte contract): programs
            # loaded vs compiled vs serialized plus the load/compile latency
            # windows the cold_start bench lane pins
            snapshot["aot"] = self._aot.stats()
        if "handoff" in snapshot:
            # export→resident transfer latency (decode-role replicas observe
            # it at import finalize); {"window": 0} until a handoff lands
            snapshot["handoff"]["transfer_ms"] = self._handoff_ms.snapshot()
        if self.timeseries is not None:
            # windowed rates over the SLO fast window (the autoscaling signal,
            # rendered as gauges in the Prometheus exposition); backlog reuses
            # the figure computed under the lock above
            fast_s = self.slo.config.fast_window_s if self.slo is not None else 60.0
            snapshot["rates"] = {
                **self.timeseries.rates(fast_s),
                "prefill_backlog_tokens": backlog,
            }
        if self.slo is not None and self.slo.armed:
            snapshot["slo"] = self.slo.evaluate(self.timeseries)
        if self._tenant_slo is not None and len(self._tenant_slo):
            # per-tenant SLO verdicts (bounded LRU of tenants with armed
            # targets): absent entirely until such a tenant sends traffic —
            # the tenancy-off byte-for-byte contract
            snapshot["tenant_slo"] = self._tenant_slo.evaluate()
        return snapshot

    def quiesce(self) -> None:
        """Stop ACCEPTING new submissions (they shed with
        :class:`QueueFullError`, which the replica scheduler routes around)
        while everything already queued or resident keeps running to
        completion — the first phase of a zero-loss scale-down; :meth:`close`
        is the second, once :meth:`occupancy` reads empty."""
        with self._lock:
            self._quiesced = True

    def close(self, wait: bool = True, timeout: float = 120.0) -> None:
        """Stop admitting new requests, DRAIN resident streams — and
        partially-prefilled admissions, which already hold a slot and paid
        prefill work — to completion, then stop the engine. Never-admitted
        pending requests get a clean end-of-stream. ``wait=False`` returns immediately while the drain
        finishes on the engine thread; ``timeout`` bounds the wait (the
        SIGTERM drain path passes its remaining drain budget here)."""
        with self._lock:
            self._closed = True
            self._lock.notify_all()
        if wait and self._thread is not None:
            self._thread.join(timeout=timeout)

    # ------------------------------------------------------------------ engine

    def _engine_loop(self) -> None:
        # every pass below is one iteration of the engine log: it starts in
        # the ``schedule`` phase, the work further down enters its own phase
        # (``with log.phase(...)``, which suspends the one around it), and
        # ``log.end()`` records it; a wait with nothing to do is ``idle``,
        # outside every iteration (docs/observability.md)
        log = self.engine_log
        register_engine_log(log)
        try:
            while True:
                log.begin()
                with self._lock:
                    while (
                        not self._closed
                        and not self._pending
                        and not self._admissions
                        and not self._sessions
                    ):
                        log.wait()
                        self._lock.wait()
                        log.begin()
                    self._apply_cancellations_locked()
                    if self._closed:
                        # no new admissions; residents — and partially
                        # prefilled admissions, which already hold a slot and
                        # paid prefill work — drain to completion
                        for _, session in self._pending:
                            self._record_end(session, "closed")
                            session.out.put(_SENTINEL)
                        self._pending.clear()
                        if not self._sessions and not self._admissions:
                            break
                self._admit_pending()
                if self._sessions:
                    self._decode_chunk()
                else:
                    self._sync_carry()  # a reaped cancel, a row that ended at admission
                log.end()
        except BaseException as exc:  # engine death must not strand consumers
            logger.error(f"continuous-batching engine failed: {exc!r}")
            # postmortem: the timelines that explain the failure leave the
            # process before the consumers see the error (no-op when no
            # recorder is installed, i.e. outside a serving app)
            from unionml_tpu.observability.recorder import dump_active

            dump_active(f"continuous engine failed: {type(exc).__name__}")
            with self._lock:
                self._closed = True
                for _, session in self._pending:
                    self._record_end(session, "error")
                    session.out.put(exc)
                for adm in self._admissions:
                    if not adm.session.finished:
                        self._record_end(adm.session, "error")
                        adm.session.out.put(exc)
                for session in self._sessions.values():
                    self._record_end(session, "error")
                    session.out.put(exc)
                self._pending.clear()
                self._admissions.clear()
                self._sessions.clear()
        finally:
            log.stop()
            with self._lock:
                for _, session in self._pending:
                    session.out.put(_SENTINEL)
                for adm in self._admissions:
                    adm.session.out.put(_SENTINEL)
                for session in self._sessions.values():
                    session.out.put(_SENTINEL)
                for session in self._ended:  # died between a row's finish and its emit's close
                    session.out.put(_SENTINEL)

    def _admit_pending(self) -> None:
        """Move waiting prompts toward residency. The lock is held ONLY for
        queue/slot/block bookkeeping — device-side prefill (seconds of work,
        tens of seconds on a first compile) runs
        unlocked so concurrent ``submit``/``close`` callers never stack behind
        it; the engine thread is the sole device-state owner, so the unlocked
        sections touch the carry safely.

        With ``admit_chunk`` set, each in-flight admission advances ONE chunk
        per pass and this method returns once ``prefill_budget`` prefill
        tokens have run — the caller's decode dispatch interleaves with long
        prefills, bounding resident streams' time-between-tokens at ~one
        chunk instead of one whole prompt. Monolithic admissions (chunking
        disabled, the sequence-parallel path, or an exact-width resume whose
        aligned width would overflow the cache) complete in a single step,
        exactly as before."""
        budget = self.prefill_budget
        spent = 0
        log = self.engine_log
        while True:
            self._start_admissions()  # in the pass's own phase, ``schedule``
            if not self._admissions:
                return
            with log.phase("admit"):
                for adm in list(self._admissions):
                    if not self._admission_alive(adm):
                        continue
                    try:
                        cost = self._admission_step(adm)
                    except ValueError as exc:
                        # a bad prompt (e.g. longer than the cache can hold) fails
                        # its own stream; the engine and other residents keep going
                        # — admission work builds only a fresh [1, ...] row and
                        # never touches the shared carry, so continuing is safe.
                        # The finished flip + enqueue happen under the lock,
                        # mirroring _cancel's guarded pattern — otherwise a
                        # concurrent _cancel could interleave its sentinel before
                        # (or instead of) the error
                        self._abort_admission(adm, exc)
                        continue
                    except BaseException as exc:
                        # engine-fatal: this session is in NEITHER _pending NOR
                        # _sessions — flag it finished and notify its queue here
                        # (the death handler skips finished sessions), then let
                        # the engine die
                        with self._lock:
                            if adm in self._admissions:
                                self._admissions.remove(adm)
                            if not adm.session.finished:
                                adm.session.finished = True
                                self._record_end(adm.session, "error")
                                adm.session.out.put(exc)
                        raise
                    spent += cost
                    log.prefill_tokens += cost
                    if adm.done:
                        log.admitted += 1
                        if adm.session.export:
                            self._export_admission(adm)
                        else:
                            self._finalize_admission(adm)
                    if budget is not None and spent >= budget:
                        return

    def _start_admissions(self) -> None:
        """Sweep dead/expired waiters, then move head-of-queue prompts into
        free slots as in-flight admissions (lock held throughout; no device
        work). Cancelled sessions' consumers already hold the sentinel; a
        session past its deadline is shed with DeadlineExceeded — its client
        has given up, so a prefill + full decode would be pure waste (the
        whole list is swept, not just the head: max_waiting bounds it, so
        this stays cheap). An admission is allocated only the prompt + first
        dispatch (residents grow lazily); the head-of-line request keeps its
        FIFO position while the pool cannot supply its initial blocks."""
        with self._lock:
            live = []
            for prompt_s, s in self._pending:
                if s.finished:
                    continue
                if expired(s.deadline):
                    s.finished = True
                    self.shed_deadline += 1
                    if self.timeseries is not None:
                        self.timeseries.sheds.add()
                    self._tenant_shed(s.tenant)
                    _tev(s, "engine.shed_deadline", phase="waiting")
                    self._record_end(s, "shed_deadline")
                    s.out.put(DeadlineExceeded(
                        "deadline exceeded while waiting for a decode slot"
                    ))
                    continue
                live.append((prompt_s, s))
            self._pending = live
            if self._closed:
                return
            # monolithic admissions never persist across steps, so the
            # concurrency cap only matters in chunked mode; keeping it at 1
            # when chunking is off preserves the historical one-at-a-time
            # pop-prefill-paste order
            limit = self.max_admissions if self.admit_chunk else 1
            while self._pending and len(self._admissions) < limit:
                self._select_pending_locked()
                if not self._free:
                    if self._preempt_for_priority_locked():
                        # the victim requeued at the head; re-select so the
                        # high-priority prompt rotates back in front of it
                        continue
                    break
                gather_row = None
                cached = 0
                pins: "List[int]" = []
                p0 = self.prefix.length if self.prefix is not None else 0
                head_prompt, head_session = self._pending[0]
                head_budget = head_session.max_new - head_session.produced
                lifetime = len(self._shared_prefix_blocks) + self._blocks_lifetime(head_prompt, head_budget)
                positions = p0 + max(len(head_prompt), 1) + head_budget
                if lifetime > self.max_blocks or positions > self.cache_len:
                    # an oversized prompt can never fit a slot (its table row, or
                    # the row cache its prefill fills): fail its stream now
                    # instead of wedging the FIFO head forever
                    prompt, session = self._pending.pop(0)
                    if not session.finished:
                        session.finished = True
                        self._record_end(session, "error")
                        session.out.put(ValueError(
                            f"prompt of length {len(prompt)} with {head_budget} new tokens needs "
                            f"{positions} KV positions ({lifetime} blocks of {self.block_size} with the "
                            f"dispatch overshoot) but a slot holds cache_len {self.cache_len} "
                            f"({self.max_blocks} blocks)"
                        ))
                    continue
                # seeded leading table entries: the static prefix's full
                # blocks, or (on a radix hit) the matched cached run
                seeded = list(self._shared_prefix_blocks)
                # imported handoffs skip the radix match: their row arrives
                # complete, so there is no prefill to skip — matching would
                # only pin blocks the gather path never reads
                if self._radix is not None and head_session.pending_import is None:
                    total = p0 + max(len(head_prompt), 1)
                    # cap at total - 1: the last prompt token always
                    # prefills so the first sampled token has its hidden
                    # state (and stays bit-identical to a cold prefill)
                    m, mblocks = self._radix.match(self._radix_key(head_prompt))
                    m = min(m, total - 1)
                    if m > p0:
                        cached = m
                        mblocks = mblocks[: -(-m // self.block_size)]
                        seeded = mblocks[: m // self.block_size]
                        # pin every matched block (the partial tail too —
                        # the gather reads it) until this stream releases
                        pins = list(mblocks)
                        self._radix.pin(pins)
                try:
                    needed = self._blocks_initial(head_prompt, head_budget, shared=len(seeded))
                    if needed > len(self._free_blocks):
                        # pool pressure: cached-but-idle prefixes are exactly
                        # the memory the next admission may take back
                        self._reclaim_blocks_locked(needed - len(self._free_blocks))
                    if needed > len(self._free_blocks):
                        if pins:
                            self._radix.release(pins)
                        return
                    prompt, session = self._pending.pop(0)
                    slot = self._free.pop(0)
                except BaseException:
                    # admission died between pin and handoff: unpin, or the
                    # matched prefix blocks stay unevictable forever
                    if pins:
                        self._radix.release(pins)
                    raise
                # the session owns the pins from here: its release path
                # (_release_slot_locked) unpins them with every other exit
                session.pins = pins
                session.slot = slot
                session.admit_seq = self._admit_counter
                self._admit_counter += 1
                session.row_start = p0 + max(len(prompt), 1)
                alloc = [self._free_blocks.pop(0) for _ in range(needed)]
                self._slot_blocks[slot] = alloc
                session.shared_blocks = len(seeded)
                session.table_len = len(seeded) + len(alloc)
                session.table = list(seeded) + list(alloc)
                blocks_row = np.full((self.max_blocks,), self._scratch_block, np.int32)
                blocks_row[: len(seeded)] = seeded
                blocks_row[len(seeded) : len(seeded) + len(alloc)] = alloc
                if cached:
                    gather_row = np.full((self.max_blocks,), self._scratch_block, np.int32)
                    gather_row[: len(pins)] = pins
                self._seed += 1
                now = time.monotonic()
                if session.admission_started is None:  # a preemption resume keeps the first
                    session.admission_started = now
                _tev(
                    session, "engine.admission_start", slot=slot,
                    queue_wait_ms=round((now - session.created_at) * 1e3, 3),
                )
                self._admissions.append(_Admission(
                    session=session,
                    prompt=prompt,
                    slot=slot,
                    seed=self._seed,
                    budget=session.max_new - session.produced,
                    blocks_row=blocks_row,
                    started_at=now,
                    start=p0,
                    cached=cached,
                    gather_row=gather_row,
                ))

    def _select_pending_locked(self) -> None:
        """Rotate the QoS-chosen waiting session to the head of ``_pending``
        (caller holds the lock). FIFO fast path: with every live waiter at
        default tenant/priority — tenancy off — nothing moves and the
        per-tenant deficit map stays empty, so the engine is byte-for-byte the
        historical one. With QoS traffic: strict priority tiers (high > normal
        > batch), and within the winning tier **deficit round robin** across
        tenants — each tenant's deficit accrues ``quantum x weight`` per round
        (quantum = the token-weighted load normalizer, one admission chunk or
        one widest bucket) and selection pays the head prompt's token cost, so
        a hostile burst drains at its fair share while the other tenants'
        requests interleave instead of queueing behind it. Zero-weight tenants
        are best-effort: they round only when no weighted tenant waits in the
        tier (their throughput is whatever their bucket rate leaves)."""
        live = [(idx, s) for idx, (_, s) in enumerate(self._pending) if not s.finished]
        if not live or all(
            s.tenant is None and s.priority == PRIORITY_NORMAL for _, s in live
        ):
            if self._drr_deficit:
                self._drr_deficit.clear()  # QoS traffic drained: drop tenant state
            return
        best_tier = min(s.priority for _, s in live)
        queues: "Dict[str, List[int]]" = {}
        for idx, s in live:
            if s.priority == best_tier:
                queues.setdefault(s.tenant or "", []).append(idx)
        for tenant in list(self._drr_deficit):
            if tenant not in queues:
                # deficits exist only for WAITING tenants: request-derived keys
                # can never grow this map past max_waiting entries
                del self._drr_deficit[tenant]
        registry = self._registry()
        weights = {
            tenant: (registry.weight(tenant) if registry is not None else 1.0)
            for tenant in queues
        }
        # zero-weight tenants are best-effort: they compete only when no
        # weighted tenant is waiting in the tier (then as plain round-robin)
        candidates = [t for t in queues if weights[t] > 0] or list(queues)

        def head_cost(tenant: str) -> float:
            return float(max(len(self._pending[queues[tenant][0]][0]), 1))

        chosen: Optional[str] = None
        if len(candidates) == 1:
            chosen = candidates[0]
        elif (
            self._drr_last in candidates
            and self._drr_deficit.get(self._drr_last, 0.0) >= head_cost(self._drr_last)
        ):
            # classic DRR: KEEP serving the pointer tenant while its banked
            # deficit covers the next head — this consecutive-service rule is
            # what makes throughput proportional to weight, not to visit count
            chosen = self._drr_last
            self._drr_deficit[chosen] -= head_cost(chosen)
        else:
            start = 0
            if self._drr_last in candidates:
                start = (candidates.index(self._drr_last) + 1) % len(candidates)
            order = candidates[start:] + candidates[:start]
            quantum = self._load_norm
            for _ in range(64):  # each full round accrues quantum x weight -> terminates
                for tenant in order:
                    # one quantum x weight granted per visit; an insufficient
                    # deficit is BANKED (the "deficit" in DRR) for next round
                    deficit = self._drr_deficit.get(tenant, 0.0) + quantum * max(
                        weights[tenant], 0.0
                    )
                    if deficit >= head_cost(tenant):
                        self._drr_deficit[tenant] = deficit - head_cost(tenant)
                        chosen = tenant
                        break
                    self._drr_deficit[tenant] = deficit
                if chosen is not None:
                    break
                if all(weights[t] <= 0 for t in order):
                    break  # nothing accrues: degrade to plain round-robin
            if chosen is None:
                chosen = order[0]
        self._drr_last = chosen
        head = queues[chosen][0]
        if head != 0:
            self._pending.insert(0, self._pending.pop(head))

    def _preempt_for_priority_locked(self) -> bool:
        """With no free slot and a HIGH-priority prompt heading the queue,
        preempt exactly one lowest-priority resident (ties: youngest — the
        block-pressure victim rule) through the engine's
        preempt/exact-width-resume path: the victim requeues at the FIFO head
        and later resumes token-identically, never truncated. Returns True
        when a slot was freed (caller re-selects)."""
        if not self._pending:
            return False
        head = self._pending[0][1]
        if head.finished or head.priority != PRIORITY_HIGH:
            return False
        victims = [
            slot for slot, s in self._sessions.items() if s.priority > head.priority
        ]
        if not victims:
            return False
        victim = max(
            victims,
            key=lambda slot: (self._sessions[slot].priority, self._sessions[slot].admit_seq),
        )
        self.priority_preemptions += 1
        self._preempt_locked(victim, reason="priority")
        return True

    def tenant_census(self) -> "Dict[str, Dict[str, int]]":
        """Live per-tenant stream counts (resident + waiting, in-flight
        admissions included) for ``/debug/fleet`` — computed on demand by
        scanning the bounded session/queue tables, so there is no per-tenant
        counter to leak or to forget to decrement. Anonymous traffic is
        omitted; the result is bounded by slots + max_waiting."""
        census: "Dict[str, Dict[str, int]]" = {}

        def bump(tenant: Optional[str], kind: str) -> None:
            if tenant is None:
                return
            entry = census.setdefault(tenant, {"resident": 0, "waiting": 0})
            entry[kind] += 1

        with self._lock:
            for session in self._sessions.values():
                bump(session.tenant, "resident")
            for _, session in self._pending:
                if not session.finished:
                    bump(session.tenant, "waiting")
            for adm in self._admissions:
                if not adm.session.finished:
                    bump(adm.session.tenant, "waiting")
        return census

    def _admission_alive(self, adm: _Admission) -> bool:
        """Drop an in-flight admission whose consumer went away (cancel) or
        whose deadline passed mid-prefill: the slot and any pool blocks come
        back immediately and the partially filled row is simply dropped — it
        was never pasted, so no device-side masking is needed. Residents are
        unaffected (a deadline governs the waiting/prefill phases only)."""
        with self._lock:
            session = adm.session
            if not session.finished and expired(session.deadline):
                session.finished = True
                self.shed_deadline += 1
                if self.timeseries is not None:
                    self.timeseries.sheds.add()
                self._tenant_shed(session.tenant)
                _tev(session, "engine.shed_deadline", phase="prefill")
                self._record_end(session, "shed_deadline")
                session.out.put(DeadlineExceeded(
                    "deadline exceeded mid-prefill; admission abandoned"
                ))
            if session.finished:
                if adm in self._admissions:
                    self._admissions.remove(adm)
                self._free.append(adm.slot)
                self._release_blocks_locked(adm.slot, session)
                return False
            return True

    def _abort_admission(self, adm: _Admission, exc: BaseException) -> None:
        """Fail one admission's stream (free the slot/blocks, notify the
        consumer) without touching the engine or other residents."""
        with self._lock:
            if adm in self._admissions:
                self._admissions.remove(adm)
            self._free.append(adm.slot)
            self._release_blocks_locked(adm.slot, adm.session)
            if not adm.session.finished:
                adm.session.finished = True
                self._record_end(adm.session, "error")
                adm.session.out.put(exc)

    def _admission_begin(self, adm: _Admission) -> int:
        """Classify an admission and set up its prefill. Monolithic paths run
        the whole prefill here through :meth:`_prefill_row` — identical
        numerics and dispatch rules to the pre-chunking engine (including the
        sequence-parallel admission and the exact-width preemption-resume
        fallback) — and return their token cost; the chunked path allocates
        the row cache(s), pads the prompt to a chunk-aligned width, and
        leaves the stepping to :meth:`_admission_step` (cost 0: no columns
        ran yet)."""
        cfg = self.gen.config
        gen = self.gen
        prompt, session = adm.prompt, adm.session
        if session.pending_import is not None:
            return self._import_begin(adm)
        dfa_state = None
        if gen._cs is not None:
            # the DFA state is a pure function of (grammar, emitted tokens):
            # a fresh admission starts at the grammar's start state, a
            # preemption resume walks the echo — the resumed row continues
            # masking exactly where the evicted one left off
            cs = gen._cs
            dfa_state = int(cs.starts[session.grammar])
            for t in session.echo:
                dfa_state = int(cs.trans[dfa_state, t])
        adm.dfa_state = dfa_state
        adm.cstate = () if dfa_state is None else (np.asarray([dfa_state], np.int32),)
        p0 = self.prefix.length if self.prefix is not None else 0
        if adm.gather_row is not None and self._begin_cached(adm):
            return 0
        if self._radix is not None:
            with self._lock:
                self.prefix_cache_misses += 1
        bucket = gen._bucket(max(len(prompt), 1))
        if p0 + bucket + adm.budget > self.cache_len:
            # a PREEMPTED request resumes as prompt + emitted tokens, which
            # can outgrow every configured bucket while still fitting the
            # cache contiguously (_start_admissions checked that it does) —
            # admit at the exact width instead of failing the stream
            # (_prefill_row applies the same rule)
            bucket = max(len(prompt), 1)
        sp = cfg.sp_prefill and gen.mesh is not None and self._sp_seq > 1 and self.prefix is None
        chunk = self.admit_chunk
        aligned = chunk_aligned(bucket, chunk) if chunk else bucket
        if not chunk or sp or p0 + aligned > self.cache_len:
            # monolithic admission: chunking disabled, a sequence-parallel
            # prefill (already spread over chips — slicing it would serialize
            # the shard_map), or an exact-width resume whose chunk-aligned
            # width would overflow the cache (the fallback keeps the resume's
            # token-exactness guarantee instead of failing the stream)
            adm.tok0, adm.row_len, adm.row_cache, adm.last, adm.d_row_cache = self._prefill_row(
                prompt, adm.seed, budget=adm.budget, dfa_state=dfa_state,
                # logprobs admissions keep the dense prefill (token-identical
                # to sp) so the last-hidden row is retained for tok0's logprob
                allow_sp=not session.want_logprobs,
            )
            adm.done = True
            with self._lock:
                self.prefill_monolithic += 1
            _tev(session, "engine.prefill", tokens=p0 + bucket, mode="monolithic")
            return p0 + bucket
        adm.chunk, adm.width = chunk, aligned
        tokens = np.full((1, aligned), cfg.pad_id, np.int32)
        tokens[0, : len(prompt)] = np.asarray(prompt, np.int32)
        adm.tokens = tokens
        # the same set-up program as _prefill_row's (one for both models: the
        # admission's length, key, flags and zeroed, prefix-seeded rows), so
        # chunked and monolithic admission sample the identical first token;
        # under speculation the draft's row chunks in LOCKSTEP with the
        # target's (same columns per step), so speculative admissions stall
        # residents no longer than plain ones
        adm.lengths, adm.key, adm.row_valid, lasts, rows = self._admission_setup(
            adm.seed, p0 + max(len(prompt), 1)
        )
        adm.last, adm.row_cache = lasts[0], rows[0]
        if self._spec is not None:
            adm.d_last, adm.d_row_cache = lasts[1], rows[1]
        return 0

    def _import_begin(self, adm: _Admission) -> int:
        """Set up an imported-handoff admission (engine thread): place the
        exported pages onto THIS engine's submesh and mark the admission
        complete — no prefill runs, so the cost is one ``device_put``. The
        grammar state is recovered from the payload's emitted tokens exactly
        as a preemption resume recovers it (the DFA is a pure function of the
        emissions), stopping one short so :meth:`_finalize_admission`'s
        standard advance past the first token lands on the right state."""
        payload = adm.session.pending_import
        if int(payload.get("block_size") or 0) != self.block_size:
            raise ValueError(
                f"handoff block_size {payload.get('block_size')} != this engine's "
                f"{self.block_size}; disaggregated replicas must be built with "
                "identical engine knobs"
            )
        if int(payload["lengths"]) > self.cache_len:
            raise ValueError(
                f"handoff covers {payload['lengths']} positions but this engine's "
                f"cache_len is {self.cache_len}; disaggregated replicas must be "
                "built with identical engine knobs"
            )
        # whole KV pages in pool layout, placed onto this engine's submesh
        # (device_put copies between disjoint device sets — and accepts the
        # numpy arrays a cross-host wire delivers)
        place = jax.device_put if self.gen.mesh is None else self.gen._place_paged_cache
        adm.import_pages = self._issue("device_put", place, tuple(dict(layer) for layer in payload["pages"]))
        # host values: they ride the paste's own dispatch
        adm.tok0 = np.asarray([int(payload["first"])], np.int32)
        adm.row_len = np.asarray([int(payload["lengths"])], np.int32)
        if self.gen._cs is not None:
            cs = self.gen._cs
            state = int(cs.starts[adm.session.grammar])
            for t in list(payload["echo"])[:-1]:
                state = int(cs.trans[state, int(t)])
            adm.dfa_state = state
            adm.cstate = (np.asarray([state], np.int32),)
        adm.done = True
        exported_at = payload.get("exported_at")
        if exported_at is not None:
            self._handoff_ms.observe(time.monotonic() - exported_at)
        _tev(
            adm.session, "engine.handoff_import",
            tokens=int(payload["lengths"]), produced=adm.session.produced,
        )
        return 0

    def _begin_cached(self, adm: _Admission) -> bool:
        """Set up a radix-cache-HIT admission: gather the matched blocks into
        a dense row and arrange chunked prefill of only the uncached suffix,
        starting at the first uncached token (an arbitrary, possibly
        non-block-aligned offset — the chunk program's ``start`` is traced, so
        this stays one compile). The gathered K/V is bit-identical to what a
        cold prefill would write at those positions (it WAS written by one),
        so the stream's tokens equal its cold-prefill run exactly. Returns
        False to fall back to the cold path when the suffix geometry would
        overflow the row (exact-width preemption resumes) — the admission then
        prefills everything but still shares the matched blocks via its
        table."""
        gen, cfg = self.gen, self.gen.config
        session = adm.session
        p0 = self.prefix.length if self.prefix is not None else 0
        total = p0 + max(len(adm.prompt), 1)
        start = adm.cached  # > p0 by the hit condition
        chunk = self._radix_chunk
        suffix = list(adm.prompt)[start - p0 :]
        width = chunk_aligned(len(suffix), chunk)
        if start + width > self.cache_len or self._carry is None:
            # (a tree hit implies a prior finalize built the carry; the None
            # check is a pure backstop)
            return False
        # the dense row materializes FROM the cached pool blocks — the exact
        # inverse of the admission's page write, one fused gather dispatch; stale
        # positions past the cached run are overwritten by the suffix prefill
        # before anything can attend to them
        # (the same program hands out the length, key and flags a cold set-up does:
        # the first sampled token is bit-identical to a cold admission's)
        adm.lengths, adm.key, adm.row_valid, (adm.last,), (adm.row_cache,) = self._issue(
            "admit_setup_cached", self._cached_setup_fn,
            self._carry[0], adm.gather_row, np.uint32(adm.seed), np.int32(total),
        )
        tokens = np.full((1, width), cfg.pad_id, np.int32)
        tokens[0, : len(suffix)] = np.asarray(suffix, np.int32)
        adm.tokens = tokens
        adm.chunk, adm.width = chunk, width
        adm.start = start
        adm.pos = 0
        with self._lock:
            self.prefix_cache_hits += 1
            self.prefix_cache_tokens_avoided += start - p0
            session.cached_tokens += start - p0
            if start % self.block_size:
                # the partially shared tail block: its matched prefix was
                # gathered into the row and will be written back into THIS
                # request's private block — copy-on-write via the row
                self.prefix_cache_cow += 1
        _tev(session, "prefill.cache_hit", tokens=start - p0, cached=start)
        return True

    def _admission_step(self, adm: _Admission) -> int:
        """Advance one admission's prefill by one unit (engine thread; device
        work runs unlocked). Monolithic admissions complete inside
        :meth:`_admission_begin`; chunked admissions run exactly one
        ``admit_chunk``-wide slice through the Generator's chunked-prefill
        program — one compile total, the chunk shape is bucket-independent —
        and sample the first token via ``_first_token`` once the last chunk
        lands. Returns the prefill tokens spent (the per-iteration budget's
        unit)."""
        gen = self.gen
        if adm.tokens is None:
            cost = self._admission_begin(adm)
            if adm.done:
                return cost
        c = adm.pos
        # host values: the chunk's columns and its offset travel with the dispatch
        sl, start = adm.tokens[:, c : c + adm.chunk], np.int32(adm.start + c)
        adm.last, adm.row_cache, counts = self._issue(
            "prefill_chunk", gen._prefill_chunk, gen.params, sl, start, adm.lengths, adm.row_cache, adm.row_valid, adm.last
        )
        if gen.counter_names:
            adm.counts.append(counts)  # read with the admission's first token: no fetch of their own
        if self._spec is not None:
            draft = self._spec._draft
            adm.d_last, adm.d_row_cache, _ = self._issue(
                "prefill_chunk", draft._prefill_chunk, draft.params, sl, start, adm.lengths,
                adm.d_row_cache, adm.row_valid, adm.d_last,
            )
        adm.pos = c + adm.chunk
        with self._lock:
            self.prefill_chunks += 1
            self.prefill_chunk_tokens += adm.chunk
        _tev(
            adm.session, "engine.prefill_chunk",
            pos=adm.pos, width=adm.width, chunk=adm.chunk,
        )
        if adm.pos >= adm.width:
            adm.tok0 = self._issue("first_token", gen._first_token, gen.params, adm.last, adm.key, *adm.cstate)
            adm.row_len = adm.lengths
            adm.done = True
        return adm.chunk

    def _export_admission(self, adm: _Admission) -> None:
        """Complete an EXPORT admission (the prefill-role path): emit the
        prompt-sampled first token, free the slot/blocks — the row never
        pastes into this engine's pool — and package the prompt's pages
        as the session's handoff payload for a decode replica's
        :meth:`import_handoff`. A request whose first token already ends the
        stream (eos, or a budget of 1) finishes right here with no handoff —
        there is nothing left to decode anywhere."""
        cfg = self.gen.config
        session, slot = adm.session, adm.slot
        # the row's length is the handoff payload's; computed long before the token, it rides the token's wait
        first, row_len = self.engine_log.fetch("export", adm.tok0, adm.row_len)
        hit_eos = cfg.eos_id is not None and int(first[0]) == cfg.eos_id
        done_now = hit_eos or session.produced + 1 >= session.max_new
        row_len_host = 0 if done_now else int(row_len[0])
        pages = None
        if not done_now:
            # ship only the ceil(lengths / block_size) pages the prompt
            # actually occupies, keyed by their position in the block run —
            # the payload scales with the prompt, not with cache_len,
            # in-process or across hosts
            n_blocks = -(-row_len_host // self.block_size)
            pages = self._issue(
                "_export_pages_impl", self._export_pages_fn, adm.row_cache, n_blocks, self.block_size, self._slot_layers
            )
        adm.row_cache = adm.last = None  # the row never leaves the engine
        with self._lock:
            if adm in self._admissions:
                self._admissions.remove(adm)
            self._free.append(slot)
            self._release_blocks_locked(slot, session)
            if session.finished:
                # cancelled (or deadline-shed) during the unlocked prefill:
                # the consumer already holds its sentinel — drop the row
                return
            session.out.put(first)
            now = time.monotonic()
            if session.produced == 0:
                self._first_token_locked(session, now)
            _tev(session, "engine.emit", tokens=1, produced=session.produced + 1)
            session.last_emit = now
            session.echo.append(int(first[0]))
            session.produced += 1
            if self.timeseries is not None:
                self.timeseries.admissions.add()
                self.timeseries.tokens.add()
            if self._tenant_slo is not None and session.tenant is not None:
                self._tenant_slo.admitted(session.tenant)
                self._tenant_slo.tokens(session.tenant, 1)
            registry = self._registry()
            if registry is not None:
                registry.charge_tokens(session.tenant, 1)
            session.finished = True
            if done_now:
                self.engine_log.finished += 1
                _tev(session, "engine.finish", produced=session.produced)
            else:
                self.handoffs_exported += 1
                session.handoff = {
                    "prompt": list(adm.prompt),
                    "first": int(first[0]),
                    "pages": pages,  # block-aligned, keyed by block position
                    "block_size": self.block_size,
                    "lengths": row_len_host,
                    "max_new": session.max_new,
                    "produced": session.produced,
                    "echo": [int(first[0])],
                    "grammar": session.grammar,
                    "deadline": session.deadline,
                    "created_at": session.created_at,
                    "trace": session.trace,
                    "tenant": session.tenant,
                    "priority": session.priority,
                    "exported_at": now,
                }
                _tev(
                    session, "engine.handoff_export",
                    tokens=row_len_host, produced=session.produced,
                )
            self._record_end(session, "finish" if done_now else "export")
            session.out.put(_SENTINEL)

    def _finalize_admission(self, adm: _Admission) -> None:
        """Paste a completed admission's row(s) into the pool and activate its
        session — the donating admit dispatches plus carry/session
        bookkeeping. ANY failure in the paste section is engine-fatal:
        donation may already have invalidated the carry's buffers, so
        treating it as a per-request failure would leave the engine decoding
        deleted arrays (or, past the carry reassignment, a freed slot's
        ride-along writes corrupting reallocated pages)."""
        cfg = self.gen.config
        session, slot = adm.session, adm.slot
        lp0: Optional[float] = None
        if session.want_logprobs and session.pending_import is None:
            # priced BEFORE the paste: the paste donates the row cache and the
            # epilogue below drops the last-hidden reference
            lp0 = self._first_logprob(adm)
        try:
            if self._carry is None:
                # the engine's one build of its carry: the pools, the flags, the key (a few programs; once a life)
                self._carry = self._issue("init_carry", self._init_carry)
            # the chunks' counts were computed long before the first token came: they ride its wait
            first, *chunk_counts = self.engine_log.fetch("first_token", adm.tok0, *adm.counts)
            for counts in chunk_counts:
                self.engine_log.count("prefill", self.gen.counter_names, counts)
            adm.counts = []
            hit_eos = cfg.eos_id is not None and int(first[0]) == cfg.eos_id
            # produced carries across preemptions; this residency adds one token.
            # An imported handoff's first token was emitted (and its eos/budget
            # endings handled) by the EXPORTING replica — it is never start-done
            imported = session.pending_import is not None
            start_done = not imported and (hit_eos or session.produced + 1 >= session.max_new)
            # the slot, its table row and the shared count are host values: the
            # runtime moves them with the paste's own dispatch (blocks_row is
            # this admission's alone: nothing edits it while the call reads it)
            blocks_row = adm.blocks_row
            row_args = (np.int32(slot), adm.tok0, adm.row_len)
            table_args = (blocks_row, np.int32(session.shared_blocks))
            if self._spec is None:
                cache, tok, lengths, done, key, *cst = self._carry
                if adm.import_pages is not None:
                    # handoff import: the pages are in pool layout already, so
                    # the page write runs alone, with no row to lay as pages
                    cache, tok, lengths, done = self._issue(
                        "_paged_page_admit_impl", self._paged_page_admit_fn,
                        cache, adm.import_pages, tok, lengths, done, *row_args, *table_args,
                    )
                else:
                    cache, tok, lengths, done = self._issue(
                        "_paged_admit_impl", self._paged_admit_fn,
                        cache, adm.row_cache, tok, lengths, done, *row_args, *table_args,
                    )
                self._carry = (cache, tok, lengths, done, key, *cst)
            else:
                t_cache, d_cache, tok, lengths, done, produced, out_buf, rounds, acc, key, *cst = self._carry
                t_cache, d_cache, out_buf, tok, lengths, done, produced = self._issue(
                    "_paged_spec_admit_impl", self._paged_spec_admit_fn,
                    t_cache, d_cache, out_buf, adm.row_cache, adm.d_row_cache,
                    tok, lengths, done, produced, *row_args, np.asarray([start_done]), np.int32(cfg.pad_id), *table_args,
                )
                self._carry = (t_cache, d_cache, tok, lengths, done, produced, out_buf, rounds, acc, key, *cst)
            # the paste wrote this slot's done flag, length and table row: what
            # the account still held for the slot (a release not yet synced)
            # is overwritten, not owed
            self._released_host[slot] = False
            self._table_host[slot] = blocks_row
            self._edited_host[slot] = False
            if adm.dfa_state is not None:
                # advance past the (constrained) prompt-sampled token and
                # activate the slot's DFA state — the carry TAIL in both the
                # plain and speculative layouts (one copy of the rule), ahead of
                # a counting model's counts in the plain one
                state = list(self._carry)
                at = -2 if self._spec is None and self.gen.counter_names else -1
                state[at] = self._issue(
                    "slot_set", self._slot_set_fn, state[at], np.int32(slot),
                    np.int32(self.gen._cs.trans[adm.dfa_state, int(first[0])]),
                )
                self._carry = tuple(state)
            # drop the row references promptly: the donated buffers are dead
            adm.row_cache = adm.d_row_cache = adm.last = adm.d_last = adm.import_pages = None
        except BaseException as exc:
            with self._lock:
                if adm in self._admissions:
                    self._admissions.remove(adm)
                if not session.finished:
                    session.finished = True
                    self._record_end(session, "error")
                    session.out.put(exc)
            raise
        with self._lock:
            if adm in self._admissions:
                self._admissions.remove(adm)
            if self._radix is not None:
                # the prompt's full blocks now hold exactly the K/V a cold
                # prefill writes — publish them for every later request that
                # shares the prefix (even a cancelled stream's prefill work is
                # a free cache fill)
                self._radix_insert_locked(adm, session)
            if session.finished:
                # cancelled during the unlocked prefill/paste window (neither
                # pending nor resident at _cancel time): the device row was
                # just activated — mask it back out and return the slot
                # instead of decoding a full budget to a dead queue
                self._free.append(slot)
                self._release_blocks_locked(slot, session)
                self._mask_slot_done(slot)
                return
            if imported:
                # the exporting replica already emitted the first token and
                # recorded TTFT; this residency only picks up decoding from
                # produced=1 — exactly the device state a mixed replica holds
                # right after its own finalize
                session.pending_import = None
                session.resident_base = 0
                session.last_emit = time.monotonic()
                if self.timeseries is not None:
                    self.timeseries.admissions.add()
                if self._tenant_slo is not None and session.tenant is not None:
                    self._tenant_slo.admitted(session.tenant)
                self.handoffs_imported += 1
            else:
                if session.want_logprobs and lp0 is not None:
                    session.lp.append(lp0)  # before the token: k tokens => >= k logprobs
                session.out.put(first)
                now = time.monotonic()
                if session.produced == 0:
                    self._first_token_locked(session, now)
                _tev(session, "engine.emit", tokens=1, produced=session.produced + 1)
                if session.last_emit is not None:
                    self._tbt.observe(now - session.last_emit)
                    if self.slo is not None:
                        self.slo.note_tbt(session.trace, (now - session.last_emit) * 1e3)
                    if self._tenant_slo is not None and session.tenant is not None:
                        self._tenant_slo.note_tbt(
                            session.tenant, session.trace, now - session.last_emit
                        )
                session.last_emit = now
                if self.timeseries is not None:
                    self.timeseries.admissions.add()
                    self.timeseries.tokens.add()
                if self._tenant_slo is not None and session.tenant is not None:
                    self._tenant_slo.admitted(session.tenant)
                    self._tenant_slo.tokens(session.tenant, 1)
                registry = self._registry()
                if registry is not None:
                    registry.charge_tokens(session.tenant, 1)
                session.echo.append(int(first[0]))
                session.resident_base = session.produced
                session.produced += 1
            self._sessions[slot] = session
            if start_done:
                # the decode body only flags done on tokens IT samples, and the
                # prompt-sampled tok0 is not one of them: _finish_locked masks
                # the row, or the freed slot would keep decoding as a zombie
                # row (and claim routed-expert capacity)
                self._finish_locked(slot).out.put(_SENTINEL)

    def _mask_slot_done(self, slot: int) -> None:
        """Release a slot on the device (engine thread only): its done flag is
        set, its table row points at the scratch block and
        its length is 0 — the freed blocks may be reallocated immediately, and
        the done row keeps issuing a ride-along K/V write per step, so scratch
        is where it must land. Recorded here, on the device with the next
        :meth:`_sync_carry`."""
        self._released_host[slot] = True
        self._table_host[slot] = self._scratch_block
        self._edited_host[slot] = True

    def _sync_carry(self) -> None:
        """Carry the edits recorded since the last call to the device: one
        dispatch of :meth:`_sync_impl`, none with nothing recorded (engine
        thread only). Called before every decode dispatch (a released row's
        ride-along write must find scratch, a grown row its new blocks) and at
        the end of every iteration, so the device agrees with the host's
        account whenever the engine thread is not inside an iteration. An
        admission that lands in between writes its slot's row itself and
        strikes the slot from the account (:meth:`_finalize_admission`)."""
        if self._carry is None or not (self._edited_host.any() or self._released_host.any()):
            return
        state = list(self._carry)
        # speculative mode keeps BOTH caches' tables (carry slots 0 and 1)
        caches = (0,) if self._spec is None else (0, 1)
        at = 2 if self._spec is None else 3  # lengths, then done
        tables = tuple(tuple(layer["table"] for layer in state[c] if "table" in layer) for c in caches)
        # the pools are never passed: their buffers stay where they are. The
        # program is handed copies: a backend may read a numpy argument in
        # place, after this thread has gone on editing it
        tables, state[at], state[at + 1] = self._issue(
            "_sync_impl", self._sync_fn,
            tables, state[at], state[at + 1], self._table_host.copy(), self._edited_host, self._released_host,
        )
        for c, synced in zip(caches, tables):
            fresh = iter(synced)  # a layer that keeps a row a slot has no table
            state[c] = tuple({**layer, "table": next(fresh)} if "table" in layer else layer for layer in state[c])
        self._carry = tuple(state)
        self._edited_host = np.zeros_like(self._edited_host)
        self._released_host = np.zeros_like(self._released_host)
        log = self.engine_log  # engine thread only, like the pass's other counters
        log.table_syncs += 1

    def _release_blocks_locked(self, slot: int, session: Optional[_Session] = None) -> None:
        """Return a slot's PRIVATE pool blocks to the allocator and release the
        session's radix pins (caller holds the lock). Tree-owned blocks the
        session's table referenced stay cached — unpinning merely makes them
        evictable again."""
        self._free_blocks.extend(self._slot_blocks.pop(slot, []))
        if session is not None and session.pins:
            self._radix.release(session.pins)
            session.pins = []

    def _reclaim_blocks_locked(self, n: int) -> None:
        """Evict least-recently-used unpinned radix runs until ``n`` more
        blocks are free (or nothing evictable remains); freed ids rejoin
        ``_free_blocks``, so cache pressure resolves before admission blocks
        and long before preemption fires (caller holds the lock)."""
        if self._radix is None or n <= 0:
            return
        self._free_blocks.extend(self._radix.evict(n))

    def _radix_insert_locked(self, adm: _Admission, session: _Session) -> None:
        """Publish a completed admission's full-token blocks into the radix
        tree (caller holds the lock). Only blocks every position of which holds
        a REAL token's K/V are insertable — the partial tail block (prompt tail
        + upcoming decode writes) stays private. Ownership of the transferred
        blocks moves to the tree; the session keeps them pinned (its table
        still reads them) until release."""
        p0 = self.prefix.length if self.prefix is not None else 0
        total = p0 + max(len(adm.prompt), 1)
        full = total // self.block_size  # table entries fully covered by real tokens
        shared = session.shared_blocks
        if full <= shared:
            return
        key = self._radix_key(adm.prompt)[: full * self.block_size]
        entry_ids = [int(b) for b in adm.blocks_row[:full]]
        kept = self._radix.insert(key, entry_ids)
        # a concurrent admission may have inserted a longer run first (kept >
        # shared): entries [shared, kept) keep their private duplicates and
        # the tree's copy wins for future matches
        lo, hi = max(kept, shared) - shared, full - shared
        if lo >= hi:
            return
        alloc = self._slot_blocks.get(adm.slot, [])
        transferred = alloc[lo:hi]
        self._slot_blocks[adm.slot] = alloc[:lo] + alloc[hi:]
        self._radix.pin(transferred)
        session.pins.extend(transferred)

    def _radix_publish_finished_locked(self, slot: int, session: _Session) -> None:
        """Decode-side insertion (caller holds the lock): publish a FINISHED
        stream's prompt + generated tokens into the radix tree — block-aligned
        only, and one token short of the emissions, because the last sampled
        token was never fed back so its K/V was never written. The leading
        blocks (static prefix, radix-matched runs, the prompt publish at
        finalize) are already in the tree, so :meth:`RadixPrefixCache.insert`
        keeps them and only the generated tail's blocks transfer; transferred
        blocks leave the slot's private allocation unpinned — cached and
        immediately evictable, like any idle prefix."""
        if not session.table or not session.prompt:
            return
        p0 = self.prefix.length if self.prefix is not None else 0
        # K/V is on device for every position before the LAST emitted token
        total = p0 + len(session.prompt) + len(session.echo) - 1
        full = total // self.block_size
        if full <= 0 or full > len(session.table):
            return
        key = self._radix_key(list(session.prompt) + list(session.echo))[: full * self.block_size]
        entries = [int(b) for b in session.table[:full]]
        kept = self._radix.insert(key, entries)
        alloc = self._slot_blocks.get(slot)
        if alloc is None:
            return
        for b in entries[kept:full]:
            # ownership of the transferred tail moves to the tree; blocks the
            # session never owned privately (tree/prefix-seeded leads) are
            # covered by kept and never reach this loop
            if b in alloc:
                alloc.remove(b)

    def _radix_reset_locked(self) -> None:
        """Drop every cached run and zero the cache counters (caller holds the
        lock; no streams may be live): warmup's junk probes must not leave
        junk prefixes cached — or hit/miss counters skewed — when real traffic
        starts. The static shared-prefix blocks are re-seeded as the tree's
        permanent root run."""
        static = set(self._shared_prefix_blocks)
        self._free_blocks.extend(b for b in self._radix.clear() if b not in static)
        self._radix.evictions = 0
        self._radix.evicted_blocks = 0
        if self._shared_prefix_blocks:
            self._radix.insert(
                list(self.prefix.tokens)[: len(self._shared_prefix_blocks) * self.block_size],
                list(self._shared_prefix_blocks),
            )
        self.prefix_cache_hits = 0
        self.prefix_cache_misses = 0
        self.prefix_cache_tokens_avoided = 0
        self.prefix_cache_cow = 0

    def _radix_key(self, prompt: Sequence[int]) -> "List[int]":
        """The radix key of a prompt: the full LOGICAL token sequence — static
        shared prefix (whose tokens the cache constructor required) followed by
        the prompt — so cached runs compose with the configured prefix and the
        prefix's partial tail block is cacheable like any other run."""
        key = list(self.prefix.tokens) if self.prefix is not None else []
        key.extend(int(t) for t in prompt)
        return key

    def cached_prefix_tokens(self, prompt: Sequence[int]) -> int:
        """Prompt tokens this engine could serve from its radix cache right
        now (0 when prefix caching is off) — beyond the static shared prefix,
        which every replica holds. The replica scheduler routes shared-prefix
        traffic on this actual per-replica number instead of guessing from a
        routing-history LRU."""
        if self._radix is None:
            return 0
        p0 = self.prefix.length if self.prefix is not None else 0
        total = p0 + max(len(prompt), 1)
        with self._lock:
            m = self._radix.match_len(self._radix_key(prompt))
        return max(0, min(m, total - 1) - p0)

    def _extend_tables(self, slot: int, start_idx: int, ids: "List[int]") -> None:
        """Append freshly allocated block ids to a resident slot's table row
        (engine thread only): recorded here, in every cache's tables with the
        next :meth:`_sync_carry`."""
        self._table_host[slot, start_idx : start_idx + len(ids)] = ids
        self._edited_host[slot] = True

    def _preempt_locked(self, slot: int, reason: str = "capacity") -> None:
        """Evict a resident under pool exhaustion (or for a higher-priority
        admission, ``reason="priority"``): free its slot/blocks, mask its row,
        and requeue it at the FIFO head as (original prompt + every token
        already emitted) — the resumed prefill's greedy continuation is
        token-identical, so the consumer never notices beyond latency. The
        cost is recomputing the evicted context once (vLLM's recompute
        preemption)."""
        session = self._sessions.pop(slot)
        self.preemptions += 1
        _tev(
            session, "engine.preempt", produced=session.produced,
            **({"reason": reason} if reason != "capacity" else {}),
        )
        self._free.append(slot)
        self._release_blocks_locked(slot, session)
        self._mask_slot_done(slot)
        session.slot = -1
        session.table = []
        if not session.finished:
            # a cancelled-but-not-yet-reaped victim is simply dropped — its
            # consumer already has the sentinel, and requeuing it would waste a
            # full prefill before admission notices it is dead
            self._pending.insert(0, (list(session.prompt) + list(session.echo), session))

    def _ensure_capacity_locked(self) -> None:
        """Lazy growth at every chunk boundary (engine thread, lock held):
        each resident's table must cover the NEXT dispatch's worst-case writes;
        when the pool cannot supply the growth, the YOUNGEST resident is
        preempted and retried — older residents keep their pages (LIFO, so
        long-running streams converge instead of thrashing). A lone resident
        can always grow to its lifetime need (pool >= max_blocks)."""
        while True:
            deficits = {}
            for slot, session in self._sessions.items():
                produced_res = session.produced - session.resident_base
                # one chunk of lookahead, capped at the session's lifetime
                # ceiling (a small remaining budget never over-grows)
                tokens = min(
                    session.row_start + max(produced_res - 1, 0) + self.decode_chunk + self._overshoot,
                    session.row_start + (session.max_new - session.resident_base) - 1 + self._overshoot,
                )
                # growth is measured against the table cursor, not the private
                # list: radix-transferred entries stay in the table after their
                # ownership moved to the tree
                target = self._table_entries(tokens)
                if target > session.table_len:
                    deficits[slot] = target - session.table_len
            need = sum(deficits.values())
            if need > len(self._free_blocks):
                # evict idle cached runs before preempting live residents
                self._reclaim_blocks_locked(need - len(self._free_blocks))
            if need <= len(self._free_blocks):
                for slot, extra in deficits.items():
                    session = self._sessions[slot]
                    alloc = [self._free_blocks.pop(0) for _ in range(extra)]
                    self._slot_blocks[slot].extend(alloc)
                    self._extend_tables(slot, session.table_len, alloc)
                    self.engine_log.blocks_grown += extra
                    session.table_len += extra
                    session.table.extend(alloc)
                return
            # lowest-priority first, youngest within a tier — with priorities
            # unset every session ties at normal and this is exactly the
            # historical LIFO (max admit_seq) victim choice
            victim = max(
                self._sessions,
                key=lambda s: (self._sessions[s].priority, self._sessions[s].admit_seq),
            )
            self._preempt_locked(victim)

    def _finish_locked(self, slot: int) -> _Session:
        """End a resident stream (caller holds the lock) and return its
        session: the caller owes it the sentinel, last, once the engine state
        is consistent."""
        session = self._sessions.pop(slot)
        if not session.finished:  # a cancelled row reaped here has its record already
            self._record_end(session, "finish")
        session.finished = True
        self.engine_log.finished += 1
        _tev(session, "engine.finish", produced=session.produced)
        self._free.append(slot)
        if self._radix is not None:
            # decode-side insertion: the finished stream's prompt + generated
            # tokens become cacheable prefix, so the next turn of a multi-turn
            # conversation cache-hits the whole prior exchange
            self._radix_publish_finished_locked(slot, session)
        self._release_blocks_locked(slot, session)
        # whether or not the device already flagged the row done (it does not
        # when the budget ran out, or the prompt-sampled token was eos): the
        # table must repoint to scratch before the blocks are reallocated
        self._mask_slot_done(slot)
        return session

    def _end_streams_locked(self) -> None:
        """Close a dispatch's ``emit`` (engine thread, lock held): the finished rows'
        releases go to the device in one program, then their consumers get the
        sentinel — last, so that whoever it wakes finds the engine state
        consistent."""
        try:
            self._sync_carry()
        finally:
            ended, self._ended = self._ended, []
            for session in ended:
                session.out.put(_SENTINEL)

    def _decode_chunk(self) -> None:
        log = self.engine_log
        with log.phase("grow"), self._lock:
            self._ensure_capacity_locked()
            # the growths, and every release since the last dispatch, in one program
            self._sync_carry()
            if not self._sessions:
                return  # growth preempted the last resident; re-admission next loop
            log.rows = len(self._sessions)
        if self._spec is not None:
            return self._spec_chunk()
        cfg = self.gen.config
        with log.phase("dispatch"):
            toks, lps, carry = self._issue(
                "decode_steps", self.gen._decode, self.gen.params, *self._carry, steps=self.decode_chunk
            )
        self._carry = carry
        log.decode_attention_path = self.gen.decode_attention_path
        # [S, chunk] tokens (the wait fences the dispatch), each one's f32 logprob, the done flags, a counting model's counts
        toks_np, lps_np, done_np, *counted = log.fetch(
            "decode", toks, lps, carry[3], *((carry[-1],) if self.gen.counter_names else ())
        )
        if counted:
            log.count("decode", self.gen.counter_names, counted[0])
        registry = self._registry()
        with log.phase("emit"), self._lock:
            self.decode_dispatches += 1
            self.decoded_rows += len(self._sessions)
            now = time.monotonic()
            for slot in list(self._sessions):
                session = self._sessions[slot]
                row = toks_np[slot]
                take = min(self.decode_chunk, session.max_new - session.produced)
                if cfg.eos_id is not None:
                    hits = np.nonzero(row[:take] == cfg.eos_id)[0]
                    if hits.size:
                        take = min(take, int(hits[0]) + 1)  # emit the eos, stop after
                if take > 0:
                    if session.want_logprobs:
                        # BEFORE the tokens enqueue: a consumer holding k
                        # tokens must always find >= k logprobs on the stream
                        session.lp.extend(float(v) for v in lps_np[slot][:take])
                    session.out.put(row[:take].copy())
                    if registry is not None:
                        # post-charge the tenant's generated-tokens bucket:
                        # stream length is unknown at admission, so emissions
                        # debit (possibly into debt) and new admissions wait
                        registry.charge_tokens(session.tenant, take)
                    if session.last_emit is not None:
                        self._tbt.observe(now - session.last_emit)
                        if self.slo is not None:
                            self.slo.note_tbt(session.trace, (now - session.last_emit) * 1e3)
                        if self._tenant_slo is not None and session.tenant is not None:
                            self._tenant_slo.note_tbt(
                                session.tenant, session.trace, now - session.last_emit
                            )
                    session.last_emit = now
                    session.echo.extend(int(t) for t in row[:take])
                    session.produced += take
                    if self.timeseries is not None:
                        self.timeseries.tokens.add(take)
                    if self._tenant_slo is not None and session.tenant is not None:
                        self._tenant_slo.tokens(session.tenant, take)
                    _tev(session, "engine.emit", tokens=take, produced=session.produced)
                if session.produced >= session.max_new or bool(done_np[slot]):
                    self._ended.append(self._finish_locked(slot))
            self._end_streams_locked()

    def _spec_chunk(self) -> None:
        """Speculative shared dispatch: one floor-driven round loop (draft gamma
        tokens, verify in one target forward, accept/reject) advances every
        resident row by >= decode_chunk tokens or to completion — concurrent
        streams share BOTH the draft and the verify dispatches."""
        spec = self._spec
        log = self.engine_log
        with log.phase("dispatch"):
            if spec._round_fn is None:
                spec._round_fn = spec._build_round()
            with self._lock:
                budget_np = np.zeros((self.slots,), np.int32)
                for slot, session in self._sessions.items():
                    # device counters are per-RESIDENCY: a resumed (preempted)
                    # session's out_buf restarted at its re-admission, so its
                    # device budget is the tokens remaining at that point
                    budget_np[slot] = session.max_new - session.resident_base
            # host values: the budgets travel with the two dispatches
            floor = self._issue(
                "spec_floor", self._spec_floor_fn, self._carry[5], budget_np, np.int32(self.decode_chunk)
            )
            state = self._issue(
                "spec_loop", spec._round_fn, spec._target.params, spec._draft.params, self._carry, floor, budget_np
            )
        self._carry = state
        log.decode_attention_path = self.gen.decode_attention_path
        # the wait for the tokens fences the dispatch
        out_np, prod_np, done_np, rounds, accepted = log.fetch("spec", state[6], state[5], state[4], state[7], state[8])
        rounds_total, accepted_total = int(rounds), int(accepted)
        registry = self._registry()
        with log.phase("emit"), self._lock:
            # fold the ride-along counters into the engine's acceptance
            # telemetry under the lock, so a concurrent stats() snapshot never
            # sees rounds advanced without the matching accepted count
            spec.rounds += rounds_total - self._spec_rounds_seen
            spec.accepted_tokens += accepted_total - self._spec_accepted_seen
            self._spec_rounds_seen, self._spec_accepted_seen = rounds_total, accepted_total
            self.decode_dispatches += 1
            self.decoded_rows += len(self._sessions)
            now = time.monotonic()
            for slot in list(self._sessions):
                session = self._sessions[slot]
                new = out_np[slot, session.produced - session.resident_base : prod_np[slot]]
                if new.size:
                    session.out.put(new.copy())
                    if registry is not None:
                        registry.charge_tokens(session.tenant, int(new.size))
                    if session.last_emit is not None:
                        self._tbt.observe(now - session.last_emit)
                        if self.slo is not None:
                            self.slo.note_tbt(session.trace, (now - session.last_emit) * 1e3)
                        if self._tenant_slo is not None and session.tenant is not None:
                            self._tenant_slo.note_tbt(
                                session.tenant, session.trace, now - session.last_emit
                            )
                    session.last_emit = now
                    session.echo.extend(int(t) for t in new)
                    session.produced = session.resident_base + int(prod_np[slot])
                    if self.timeseries is not None:
                        self.timeseries.tokens.add(int(new.size))
                    if self._tenant_slo is not None and session.tenant is not None:
                        self._tenant_slo.tokens(session.tenant, int(new.size))
                    _tev(session, "engine.emit", tokens=int(new.size), produced=session.produced)
                if bool(done_np[slot]):
                    self._ended.append(self._finish_locked(slot))
            self._end_streams_locked()

