"""Data-parallel replica serving: N continuous engines behind one scheduler.

The continuous engine (:mod:`unionml_tpu.serving.continuous`) shards over
model/TP axes only — a ``[1, ...]`` admission row cannot split a batch axis, so
a mesh with ``data``/``fsdp`` > 1 used to be rejected outright and multi-chip
serving was TP-only. At fleet scale the first knob an operator reaches for is
the other one: *replicas*. Orca (OSDI '22) and vLLM (SOSP '23) both assume the
iteration-level scheduler sits above a pool of replicated engines; this module
is that layer.

Design:

- :func:`slice_mesh` cuts the device mesh along its batch axes (``dcn_data``,
  ``data``, ``fsdp``) into per-replica TP submeshes — each keeps the full axis
  set with batch axes at 1, so every Generator code path (TP collectives,
  sequence-parallel prefill, paged pools) runs unchanged inside a replica;
- :class:`ReplicaSet` builds one Generator + :class:`ContinuousBatcher` per
  submesh (params re-placed per slice; within a replica the batch axes are 1,
  so placement replicates) and owns their shared lifecycle (warmup in
  parallel, drain on close);
- :class:`ReplicaScheduler` admits requests least-loaded-first — load is a
  replica's live residents plus live waiters PLUS its pending prefill
  backlog in tokens (``ContinuousBatcher.load()``'s token weighting), so two
  replicas with equal waiter counts but a 10k-token vs a 10-token queued
  prompt do not tie — with prefix-affinity routing so shared-prefix
  requests land on the replica whose KV pool already holds that prefix. With
  per-engine radix prefix caches on (``prefix_cache=True``), affinity routes
  on each replica's ACTUAL cached-prefix length for the prompt (the radix
  probe ``cached_prefix_tokens``) — the scheduler is the cross-replica tier
  of the same cache; without them the bounded-LRU token-key heuristic
  (``affinity_tokens``) remains the fallback. The affinity margin check and
  the hotspot fallback rank on the SAME token-weighted loads, so a fallback
  never lands on a replica with a deep prefill backlog that mere waiter
  counts would hide.

- **disaggregation** (DistServe's prefill/decode split, docs/serving.md
  "Disaggregated and elastic serving"): replicas may carry a role —
  ``prefill``, ``decode``, or ``mixed`` (the default, today's behavior) via
  ``roles=``/``serve --replica-roles``. Prompts above ``prefill_threshold``
  tokens admit on a prefill replica with the engine's ``export_handoff`` and
  their finished KV pages hand off to a decode replica
  (:meth:`ContinuousBatcher.import_handoff`) — token-identical to a mixed
  replica, but resident decode streams never stall behind the prefill; warm
  multi-turn prompts whose radix-cached run on a decode replica already
  covers most of the prompt admit there directly (the shortcut);
- **elasticity**: :meth:`ReplicaSet.scale_to` grows the fleet onto spare
  submeshes (params re-placed, engine warmed BEFORE joining the scheduler)
  or drains the tail replica with zero in-flight loss (quiesce → drain →
  close, PR 1's machinery per replica), and an optional watermark autoscaler
  rides the windowed load/health signal (PR 8) to do it automatically.

Overload posture composes with PR 1's machinery: an expired deadline sheds
before routing (:class:`DeadlineExceeded`, HTTP 503), and a prompt is shed
with :class:`QueueFullError` (HTTP 429) only when EVERY replica's bounded
waiting queue is full — the scheduler walks replicas in load order, so a
single hot replica never turns away work the rest of the fleet could take.

``ContinuousBatcher(generator, ...)`` with a dp>1 mesh (or with the serve
CLI's ``--dp-replicas`` exported) transparently constructs a ReplicaSet —
existing apps opt into replica serving by mesh shape or CLI flag, with no code
changes; the set mirrors the engine's public surface (``submit`` / ``warmup``
/ ``stats`` / ``close``).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.defaults import (
    REPLICA_ROLES,
    serve_autoscale_high,
    serve_autoscale_interval_s,
    serve_autoscale_low,
    serve_dp_replicas,
    serve_max_replicas,
    serve_min_replicas,
    serve_prefill_threshold,
    serve_replica_roles,
)
from unionml_tpu.observability.trace import current_trace
from unionml_tpu.parallel.mesh import BATCH_AXES
from unionml_tpu.serving.continuous import KV_BLOCK_SIZE, ContinuousBatcher
from unionml_tpu.serving.overload import (
    DeadlineExceeded,
    QueueFullError,
    TenantThrottled,
    expired,
)
from unionml_tpu.serving.tenancy import current_tenant

__all__ = ["ReplicaScheduler", "ReplicaSet", "dp_extent", "slice_mesh"]


def dp_extent(mesh: Any) -> int:
    """Product of a mesh's batch (data-parallel) axis sizes — the natural
    replica count of :func:`slice_mesh`. 1 for ``None`` or a TP-only mesh."""
    if mesh is None:
        return 1
    extent = 1
    for axis in BATCH_AXES:
        extent *= int(mesh.shape.get(axis, 1))
    return extent


def slice_mesh(mesh: Any, replicas: Optional[int] = None) -> "List[Any]":
    """Slice a device mesh along its batch axes into per-replica TP submeshes.

    Each submesh keeps the mesh's full axis-name set with every batch axis at
    size 1 (``sequence``/``expert``/``pipe`` extents unchanged), so a
    Generator built over it behaves exactly like a TP-only engine. With
    ``replicas`` equal to the batch-axis product (the default), each replica
    owns exactly one batch slice. A SMALLER ``replicas`` that **divides** the
    product builds a hybrid mesh per replica (the T5X device-regrouping
    shape): the leftover batch extent folds into the ``model`` axis, so 2
    replicas over a dp=4×tp=2 mesh each serve tp=4 — fewer, fatter replicas
    from the same chips. Any other count raises a :class:`ValueError` naming
    the batch-axis extents (historically this surfaced as an opaque reshape
    error deep in mesh construction).
    """
    from jax.sharding import Mesh

    names = tuple(mesh.axis_names)
    devices = np.asarray(mesh.devices)
    batch_dims = [i for i, n in enumerate(names) if n in BATCH_AXES and devices.shape[i] > 1]
    total = int(np.prod([devices.shape[i] for i in batch_dims])) if batch_dims else 1
    if replicas is None:
        replicas = total
    extents = ", ".join(
        f"{names[i]}={devices.shape[i]}" for i in batch_dims
    ) or "none > 1"
    if replicas < 1 or total % replicas:
        raise ValueError(
            f"replicas ({replicas}) must divide the mesh's data-parallel extent ({total}; "
            f"batch axes: {extents}) — each replica owns a whole number of batch slices, "
            "with any leftover extent folded into the model axis"
        )
    if total == 1:
        return [mesh]
    group = total // replicas
    if group > 1 and "model" not in names:
        raise ValueError(
            f"cannot group {group} batch slices per replica: the mesh has no 'model' "
            f"axis to fold the leftover extent (batch axes: {extents}) into"
        )
    batch_shape = tuple(devices.shape[i] for i in batch_dims)
    # batch axes to the front, flattened: grouped[g] is one batch slice's devices
    grouped = np.moveaxis(devices, batch_dims, range(len(batch_dims))).reshape(
        (total,) + tuple(
            devices.shape[i] for i in range(devices.ndim) if i not in batch_dims
        )
    )
    rest_names = [names[i] for i in range(devices.ndim) if i not in batch_dims]
    out = []
    for r in range(replicas):
        sub = grouped[r * group : (r + 1) * group]
        if group > 1:
            # fold the grouped batch extent into the model axis: move the
            # group dim to just before model, then merge the two
            m = rest_names.index("model")
            sub = np.moveaxis(sub, 0, m)
            shape = list(sub.shape)
            shape[m : m + 2] = [shape[m] * shape[m + 1]]
            sub = sub.reshape(shape)
        else:
            sub = sub[0]
        # re-expand to the full axis-name set with batch axes at size 1 (the
        # remaining dims keep their relative order, so inserting 1s is exact)
        final = [1] * len(names)
        for i, name in enumerate(names):
            if i not in batch_dims:
                final[i] = sub.shape[rest_names.index(name)]
        out.append(Mesh(sub.reshape(final), names))
    return out


class ReplicaScheduler:
    """Least-loaded-first routing over N replicas, with optional prefix affinity.

    Load is supplied by the caller per decision (the engine's token-weighted
    ``load()``: live residents + live waiters + prefill backlog tokens
    normalized by the admission chunk — ints or floats both rank); ties break
    toward the lowest index, so an idle fleet fills in order and drains
    evenly. Both the affinity-margin comparison and the hotspot-fallback
    ranking use these same loads, so mixed prompt lengths route sensibly on
    every path. ``affinity_tokens > 0`` enables prefix-affinity
    routing: requests sharing their first ``affinity_tokens`` prompt tokens are
    steered to the replica that last served that prefix — its KV pool already
    holds those pages (the shared prefix's among them), so the prefill
    is warm — unless that replica is more than ``affinity_margin`` requests
    busier than the least-loaded one. The margin keeps a popular prefix from
    turning one replica into a hotspot while the rest idle; the affinity map is
    a bounded LRU, so unbounded prefix cardinality cannot grow host memory.
    """

    def __init__(
        self,
        replicas: int,
        *,
        affinity_tokens: int = 0,
        affinity_margin: int = 2,
        affinity_capacity: int = 4096,
        tenant_affinity_capacity: int = 1024,
    ):
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        if affinity_tokens < 0 or affinity_margin < 0 or affinity_capacity < 1:
            raise ValueError("affinity knobs must be non-negative (capacity >= 1)")
        if tenant_affinity_capacity < 1:
            raise ValueError("tenant_affinity_capacity must be >= 1")
        self.replicas = replicas
        self.affinity_tokens = affinity_tokens
        self.affinity_margin = affinity_margin
        self._affinity_capacity = affinity_capacity
        self._affinity: "OrderedDict[Tuple[int, ...], int]" = OrderedDict()
        #: TENANT session affinity (ROADMAP 4(b)): tenant id -> the replica
        #: that last served it. A tenant's recent sessions left their KV in
        #: that replica's radix tier, so landing its next request there is a
        #: warm prefill even when the new prompt shares no prefix the radix
        #: PROBE can see yet (a fresh conversation). Bounded LRU — the TPU009
        #: discipline — and margin-gated exactly like prefix affinity, so a
        #: single heavy tenant cannot hotspot one replica while siblings idle.
        self._tenant_affinity_capacity = tenant_affinity_capacity
        self._tenant_affinity: "OrderedDict[str, int]" = OrderedDict()
        self._lock = threading.Lock()
        #: routing telemetry: successful submissions per replica, and how many
        #: rode the affinity maps vs plain least-loaded
        self.submitted = [0] * replicas
        self.affinity_hits = 0
        self.tenant_affinity_hits = 0

    def _key(self, prompt: Optional[Sequence[int]]) -> Optional[Tuple[int, ...]]:
        if not self.affinity_tokens or prompt is None:
            return None
        if len(prompt) < self.affinity_tokens:
            return None  # shorter than the affinity window: nothing shared to exploit
        return tuple(int(t) for t in prompt[: self.affinity_tokens])

    def resize(self, replicas: int) -> None:
        """Track an elastic fleet resize: per-replica telemetry follows the
        index alignment (the replica layer adds/removes at the TAIL, so kept
        indexes keep their counts); affinity entries pointing past the new
        count are dropped — their replica is gone."""
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        with self._lock:
            if replicas > len(self.submitted):
                self.submitted.extend([0] * (replicas - len(self.submitted)))
            else:
                del self.submitted[replicas:]
                self._affinity = OrderedDict(
                    (key, idx) for key, idx in self._affinity.items() if idx < replicas
                )
                self._tenant_affinity = OrderedDict(
                    (t, idx) for t, idx in self._tenant_affinity.items() if idx < replicas
                )
            self.replicas = replicas

    def order(
        self,
        loads: Sequence[int],
        prompt: Optional[Sequence[int]] = None,
        cached: Optional[Sequence[int]] = None,
        breaching: Optional[Sequence[bool]] = None,
        deprioritized: Optional[Sequence[bool]] = None,
        tenant: Optional[str] = None,
    ) -> "Tuple[List[int], Any]":
        """``(indices to try best-first, head_is_affinity)``. The caller walks
        the list so a full (QueueFullError) replica falls through to the
        next-least-loaded instead of shedding work the rest of the fleet could
        take; the flag marks whether the head came from affinity routing (for
        hit accounting) rather than pure load order.

        ``cached`` — per-replica ACTUAL cached-prefix token counts (each
        engine's ``cached_prefix_tokens(prompt)`` radix probe) — takes
        precedence over the token-key LRU heuristic: the replica whose KV pool
        already holds the longest run of this prompt is preferred, unless it
        is more than ``affinity_margin`` load units busier than the least
        loaded (the same hotspot guard). The LRU map remains the fallback for
        engines without a prefix cache.

        ``breaching`` — per-replica SLO-breach flags (each engine's
        ``health()["state"] == "breach"``, the observability→routing feedback)
        — deprioritizes a breaching replica below EVERY non-breaching one
        regardless of load, and disqualifies it from heading the order via
        affinity: sending a warm-prefix request to a replica that is already
        missing its latency targets would trade a prefill for a breach. A
        breaching replica still appears in the walk order, so a fleet that is
        breaching everywhere degrades to plain least-loaded rather than
        shedding.

        ``deprioritized`` — per-replica role-mismatch flags from the
        disaggregated fleet (a prefill-role replica should not take
        decode-resident work unless everyone suited is full) — merges with
        ``breaching``: flagged replicas sort below every unflagged one but
        stay in the walk order, the same degrade-don't-shed posture.

        ``tenant`` — the submitting tenant id — arms TENANT session affinity
        as the LAST fallback: when neither an actual radix probe nor the
        prefix-key map produced a warm head, the replica that last served
        this tenant is preferred under the same margin gate (its radix tier
        holds the tenant's recent sessions' KV — the multi-turn-chat warmth a
        prefix probe on a brand-new prompt cannot see). A tenant-affinity
        head is flagged ``"tenant"`` (truthy, distinct from the prefix
        paths' ``True``) so :meth:`note` can account it separately."""
        avoid = (
            [bool(flag) for flag in breaching]
            if breaching is not None and len(breaching) == len(loads)
            else [False] * len(loads)
        )
        if deprioritized is not None and len(deprioritized) == len(loads):
            avoid = [a or bool(d) for a, d in zip(avoid, deprioritized)]
        ranked = sorted(range(len(loads)), key=lambda i: (avoid[i], loads[i], i))
        if cached is not None and len(cached) == len(loads) and max(cached, default=0) > 0:
            # warm replicas that are NOT breaching compete on cached length; a
            # breaching replica's warm cache never heads the order
            candidates = [i for i in range(len(loads)) if cached[i] > 0 and not avoid[i]]
            if candidates:
                preferred = min(candidates, key=lambda i: (-cached[i], loads[i], i))
                if loads[preferred] <= loads[ranked[0]] + self.affinity_margin:
                    return [preferred] + [i for i in ranked if i != preferred], True
            return self._tenant_head(ranked, loads, avoid, tenant)
        key = self._key(prompt)
        if key is not None:
            with self._lock:
                preferred = self._affinity.get(key)
            if (
                preferred is not None
                and not avoid[preferred]
                and loads[preferred] <= loads[ranked[0]] + self.affinity_margin
            ):
                return [preferred] + [i for i in ranked if i != preferred], True
        return self._tenant_head(ranked, loads, avoid, tenant)

    def _tenant_head(
        self,
        ranked: "List[int]",
        loads: Sequence[int],
        avoid: "List[bool]",
        tenant: Optional[str],
    ) -> "Tuple[List[int], Any]":
        """The tenant-session-affinity fallback head (see :meth:`order`)."""
        if tenant is None or not ranked:
            return ranked, False
        with self._lock:
            preferred = self._tenant_affinity.get(tenant)
        if (
            preferred is not None
            and preferred < len(loads)
            and not avoid[preferred]
            and loads[preferred] <= loads[ranked[0]] + self.affinity_margin
        ):
            return [preferred] + [i for i in ranked if i != preferred], "tenant"
        return ranked, False

    def note(
        self,
        replica: int,
        prompt: Optional[Sequence[int]] = None,
        *,
        affinity: Any = False,
        tenant: Optional[str] = None,
    ) -> None:
        """Record a successful routing decision (updates the affinity maps).
        ``affinity`` is the head flag :meth:`order` returned when this replica
        was its head — ``True`` counts a prefix/probe hit, ``"tenant"`` a
        tenant-session hit."""
        key = self._key(prompt)
        with self._lock:
            if replica >= len(self.submitted):
                # a routing snapshot can outlive a concurrent resize by a few
                # microseconds; re-grow rather than drop the count
                self.submitted.extend([0] * (replica + 1 - len(self.submitted)))
            self.submitted[replica] += 1
            if affinity == "tenant":
                self.tenant_affinity_hits += 1
            elif affinity:
                self.affinity_hits += 1
            if key is not None:
                self._affinity[key] = replica
                self._affinity.move_to_end(key)
                while len(self._affinity) > self._affinity_capacity:
                    self._affinity.popitem(last=False)
            if tenant is not None:
                self._tenant_affinity[tenant] = replica
                self._tenant_affinity.move_to_end(tenant)
                while len(self._tenant_affinity) > self._tenant_affinity_capacity:
                    self._tenant_affinity.popitem(last=False)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "policy": "least-loaded",
                "submitted": list(self.submitted),
                "affinity_tokens": self.affinity_tokens,
                "affinity_hits": self.affinity_hits,
                "affinity_entries": len(self._affinity),
                "tenant_affinity_hits": self.tenant_affinity_hits,
                "tenant_affinity_entries": len(self._tenant_affinity),
            }


class ReplicaSet:
    """N data-parallel :class:`ContinuousBatcher` replicas behind one scheduler.

    >>> rs = ReplicaSet.build(module, params, gen_config,
    ...                       mesh=MeshSpec(data=2, model=2).build(),
    ...                       partition_rules=llama_partition_rules(),
    ...                       slots=4, decode_chunk=8)
    >>> for chunk in rs.submit([1, 5, 9]):
    ...     ...
    >>> rs.close()

    The public surface mirrors the single engine (``submit`` / ``warmup`` /
    ``stats`` / ``close``), so everything that composes with a
    ``ContinuousBatcher`` — the stream-predictor route, ``/metrics``, graceful
    drain — composes with a replica set unchanged. Engine knobs (``slots``,
    ``decode_chunk``, ``block_size``, ``pool_blocks``, ``max_waiting``,
    ``admit_chunk``/``prefill_budget``/``max_admissions`` — stall-free
    admission — ``prefix_cache`` — the radix prefix cache, see
    serving/continuous.py — ``slo`` — the fleet health & SLO engine —
    and ``prefix``) apply PER REPLICA; a shared ``prefix`` (token ids or a
    ``PrefixCache`` built with ``cache_prefix``) is prefilled once per replica
    at construction, since cache rows cannot cross submeshes.

    ``roles`` (``{"prefill": 1, "decode": 3}``, a per-replica list, or the
    ``serve --replica-roles`` export) splits the fleet into a prefill tier and
    a decode tier with KV handoff between them; ``prefill_threshold`` sets
    the prompt length that takes the disaggregated path; ``autoscale`` (a
    watermark dict, ``None`` = the ``UNIONML_TPU_AUTOSCALE_*`` exports,
    ``False`` = off) arms the elastic-resize loop around :meth:`scale_to`.
    All three default to today's symmetric, fixed fleet.
    """

    def __init__(
        self,
        generators: Optional[Sequence[Any]] = None,
        *,
        engines: Optional[Sequence[Any]] = None,
        slots: int = 4,
        decode_chunk: int = 8,
        prefix: Optional[Any] = None,
        block_size: int = KV_BLOCK_SIZE,
        pool_blocks: Optional[int] = None,
        max_waiting: Optional[int] = None,
        admit_chunk: Optional[int] = None,
        prefill_budget: Optional[int] = None,
        max_admissions: Optional[int] = None,
        affinity_tokens: int = 0,
        affinity_margin: int = 2,
        trace: Optional[bool] = None,
        prefix_cache: Optional[bool] = None,
        slo: Optional[Any] = None,
        roles: Optional[Any] = None,
        prefill_threshold: Optional[int] = None,
        autoscale: Optional[Any] = None,
        tenancy: Optional[Any] = None,
        aot: Optional[Any] = None,
    ):
        if (generators is None) == (engines is None):
            raise ValueError("pass exactly one of generators= or engines=")
        prefix_tokens = self._prefix_tokens(prefix) if generators is not None else None
        count = len(list(engines)) if engines is not None else len(list(generators))
        self._roles = self._resolve_roles(roles, count)
        has_roles = any(r != "mixed" for r in self._roles)
        #: engine knobs retained for elastic scale-up (a new replica must be
        #: built exactly like its siblings — the KV-handoff width contract)
        self._engine_kwargs = dict(
            slots=slots, decode_chunk=decode_chunk, block_size=block_size,
            pool_blocks=pool_blocks, max_waiting=max_waiting, admit_chunk=admit_chunk,
            prefill_budget=prefill_budget, max_admissions=max_admissions,
            trace=trace, prefix_cache=prefix_cache, slo=slo, tenancy=tenancy,
            aot=aot,
        )
        self._prefix_tokens_saved = prefix_tokens
        if engines is not None:
            self._batchers: "List[Any]" = list(engines)
            if has_roles:
                for batcher, role in zip(self._batchers, self._roles):
                    batcher.role = role
        else:
            self._batchers = []
            try:
                for gen, role in zip(generators, self._roles):
                    self._batchers.append(
                        self._new_engine(gen, role if has_roles else None)
                    )
            except BaseException:
                for batcher in self._batchers:
                    batcher.close(wait=False)
                raise
        if not self._batchers:
            raise ValueError("a ReplicaSet needs at least one replica")
        self._scheduler = ReplicaScheduler(
            len(self._batchers), affinity_tokens=affinity_tokens, affinity_margin=affinity_margin
        )
        self._lock = threading.Lock()
        #: serializes resizes (scale_to callers + the autoscaler thread); the
        #: plain lock above stays counter/snapshot-granular so routing never
        #: waits behind a multi-second drain
        self._scale_lock = threading.Lock()
        #: prompt-length threshold for the disaggregated path: admissions at
        #: least this long route to a prefill-role replica and hand their KV
        #: off to a decode replica (0 = every admission, once roles exist)
        if prefill_threshold is None:
            prefill_threshold = serve_prefill_threshold()
        if prefill_threshold < 0:
            raise ValueError("prefill_threshold must be >= 0")
        self._prefill_threshold = int(prefill_threshold)
        #: per-replica mesh each engine was placed on (None when unknown —
        #: e.g. hand-built engines); scale-down returns it to the spare pool
        self._replica_meshes: "List[Any]" = [None] * len(self._batchers)
        #: construction template for scale-up (set by build()/from_generator;
        #: None = scale_to can only shrink)
        self._scale_template: "Optional[Dict[str, Any]]" = None
        #: fleet-level sheds: a deadline that expired before routing, and
        #: prompts turned away because EVERY replica's bounded queue was full
        #: (per-replica counters additionally record each engine's own sheds)
        self.shed_deadline = 0
        self.shed_queue_full = 0
        #: routing decisions that walked past an SLO-breaching replica that
        #: pure load order would have picked (the observability→routing
        #: feedback loop, made observable itself)
        self.breach_avoided = 0
        #: disaggregated-routing telemetry: admissions sent down the
        #: prefill→decode handoff path, and warm multi-turn prompts admitted
        #: directly on the decode replica whose radix cache already held them
        self.handoff_routes = 0
        self.handoff_shortcuts = 0
        #: elastic-resize telemetry
        self.scaled_up = 0
        self.scaled_down = 0
        # ---- autoscaler (env-armed by default, the --slo-* contract):
        # None reads the UNIONML_TPU_AUTOSCALE_* exports, a dict overrides
        # them, False disables the loop entirely
        self._autoscale: "Optional[Dict[str, Any]]" = None
        self._autoscale_stop = threading.Event()
        self._autoscale_thread: Optional[threading.Thread] = None
        if autoscale is None:
            high = serve_autoscale_high()
            if high > 0:
                self.configure_autoscaler(
                    high=high,
                    low=serve_autoscale_low(),
                    interval_s=serve_autoscale_interval_s(),
                    min_replicas=serve_min_replicas(),
                    max_replicas=serve_max_replicas(),
                )
        elif autoscale is not False:
            if not isinstance(autoscale, dict):
                raise TypeError(
                    f"autoscale must be a dict of watermarks, None (read the "
                    f"UNIONML_TPU_AUTOSCALE_* exports) or False, got {type(autoscale).__name__}"
                )
            self.configure_autoscaler(**autoscale)

    @staticmethod
    def _prefix_tokens(prefix: Optional[Any]) -> "Optional[List[int]]":
        if prefix is None:
            return None
        tokens = getattr(prefix, "tokens", prefix)  # PrefixCache or raw ids
        if tokens is None:
            raise ValueError(
                "a shared prefix for a ReplicaSet needs its token ids (build it with "
                "cache_prefix(...) or pass the ids directly); hand-built PrefixCaches "
                "cannot be re-prefilled per replica"
            )
        return [int(t) for t in tokens]

    @staticmethod
    def _resolve_roles(roles: Optional[Any], count: int) -> "List[str]":
        """Per-replica role list from a ``{role: count}`` dict, an explicit
        per-replica list, or (``None``) the ``serve --replica-roles`` export.
        Explicit specs that do not sum to the fleet size raise; the
        env-derived spec warns and falls back to an all-mixed fleet (the
        warn-and-degrade contract every serve export follows). Expansion
        order is prefill, then decode, then mixed — so scale-down (which
        drains the TAIL) sheds capacity replicas before the prefill tier."""
        strict = roles is not None
        if roles is None:
            roles = serve_replica_roles() or None
        if roles is None:
            return ["mixed"] * count
        if isinstance(roles, dict):
            bad = [r for r in roles if r not in REPLICA_ROLES]
            if bad:
                raise ValueError(f"unknown replica roles {bad}; expected {REPLICA_ROLES}")
            expanded: "List[str]" = []
            for role in ("prefill", "decode", "mixed"):
                expanded.extend([role] * int(roles.get(role, 0)))
        else:
            expanded = [str(r) for r in roles]
            bad = [r for r in expanded if r not in REPLICA_ROLES]
            if bad:
                raise ValueError(f"unknown replica roles {bad}; expected {REPLICA_ROLES}")
        problem = None
        if len(expanded) != count:
            problem = (
                f"replica roles {expanded} cover {len(expanded)} replicas but the fleet has {count}"
            )
        elif expanded and all(r == "prefill" for r in expanded):
            problem = (
                "an all-prefill fleet has nowhere to hand decode work off to; "
                "include at least one decode or mixed replica"
            )
        if problem:
            if strict:
                raise ValueError(problem)
            logger.warning(f"ignoring {problem}; falling back to a symmetric (all-mixed) fleet")
            return ["mixed"] * count
        return expanded

    def _new_engine(self, gen: Any, role: Optional[str]) -> Any:
        """One per-replica engine from a placed Generator — construction and
        elastic scale-up build through the same path, so a scaled-up replica
        is knob-identical to its siblings (the KV-handoff width contract)."""
        prefix_tokens = self._prefix_tokens_saved
        return ContinuousBatcher._single(
            gen,
            prefix=gen.cache_prefix(prefix_tokens) if prefix_tokens else None,
            role=role,
            **self._engine_kwargs,
        )

    # ------------------------------------------------------------------ construction

    @classmethod
    def build(
        cls,
        module: Any,
        params: Any,
        config: Any,
        *,
        mesh: Optional[Any] = None,
        partition_rules: Optional[Any] = None,
        quantize: Optional[str] = None,
        replicas: Optional[int] = None,
        **engine_kwargs: Any,
    ) -> "ReplicaSet":
        """Build per-replica Generators and engines from one set of weights.

        With a dp>1 ``mesh``, the replica count defaults to the mesh's
        data-parallel extent and each replica owns one TP submesh from
        :func:`slice_mesh`; a SMALLER ``replicas`` runs on the first N
        submeshes and keeps the rest as SPARES — the headroom
        :meth:`scale_to` and the autoscaler place new replicas onto at
        runtime. Without a dp mesh (``mesh`` is ``None`` or TP-only),
        ``replicas`` (default: the ``serve --dp-replicas`` export, else the
        ``--replica-roles`` total, else 1) engines are placed round-robin
        over the visible devices — each replica gets its own single-device
        mesh, so N chips serve N independent decode loops from one process.
        """
        from unionml_tpu.models.generate import Generator

        if replicas is None:
            replicas = serve_dp_replicas() or None
        if replicas is None:
            # a role spec implies its own fleet size (prefill=1,decode=3 = 4)
            roles_kw = engine_kwargs.get("roles")
            if isinstance(roles_kw, dict):
                replicas = sum(roles_kw.values()) or None
            elif isinstance(roles_kw, (list, tuple)):
                replicas = len(roles_kw) or None
            elif roles_kw is None:
                replicas = sum(serve_replica_roles().values()) or None
        spares: "List[Any]" = []
        if mesh is not None and dp_extent(mesh) > 1:
            all_submeshes = slice_mesh(mesh)
            mesh_procs = {d.process_index for d in np.asarray(mesh.devices).ravel()}
            if len(mesh_procs) > 1:
                # process-aware fleets (docs/serving.md "Multi-host fleets"): a
                # hybrid ICI/DCN mesh spans hosts, but one process can only
                # drive its OWN devices — keep the host-local submeshes and let
                # the cluster coordinator route across hosts. Replica counts
                # are then per host (the cross-host agreement in
                # serving/cluster.py hands every host the same number).
                from unionml_tpu.parallel.mesh import process_local_submeshes

                local = process_local_submeshes(all_submeshes)
                if not local:
                    raise ValueError(
                        "no replica submesh of this mesh is local to this process — "
                        "put the replica axes (dcn_data/data) on DCN "
                        "(MeshSpec.build_hybrid) so each batch slice stays host-local"
                    )
                logger.info(
                    f"multi-process mesh: this host owns replica submeshes "
                    f"{[index for index, _ in local]} of {len(all_submeshes)}"
                )
                all_submeshes = [sub for _, sub in local]
            extent = len(all_submeshes)
            if replicas is None:
                replicas = extent
            if replicas > extent:
                raise ValueError(
                    f"replicas ({replicas}) exceed the mesh's {'host-local ' if len(mesh_procs) > 1 else ''}"
                    f"data-parallel extent ({extent}); "
                    "a dp mesh cannot host more replicas than batch slices"
                )
            submeshes, spares = all_submeshes[:replicas], all_submeshes[replicas:]
        elif replicas is None or replicas == 1:
            submeshes = [mesh]
        elif mesh is not None:
            # a TP-only mesh replicated N times shares its device set — the
            # engines time-slice the same chips. Legitimate when serving is
            # host-dispatch-bound, surprising otherwise; say so once.
            logger.warning(
                f"ReplicaSet.build: {replicas} replicas over one TP-only mesh share "
                "its devices (time-sliced); add a data axis to give each replica its own chips"
            )
            submeshes = [mesh] * replicas
        else:
            submeshes = cls._single_device_meshes(replicas)
        generators = [
            Generator(module, params, config, mesh=sm, partition_rules=partition_rules, quantize=quantize)
            for sm in submeshes
        ]
        rs = cls(generators, **engine_kwargs)
        rs._replica_meshes = list(submeshes)
        rs._scale_template = {
            "module": module,
            "params": params,
            "config": config,
            "partition_rules": partition_rules,
            "quantize": quantize,
            "spares": spares,
            # a mesh-less build places replicas on per-device meshes round-
            # robin; scale-up keeps doing exactly that, so spares never run out
            "meshless": mesh is None,
        }
        return rs

    @staticmethod
    def _single_device_meshes(replicas: int) -> "List[Any]":
        """One full-axis-set 1-device mesh per replica, round-robin over the
        visible devices (the :func:`single_device_mesh` shape, one per chip)."""
        import jax
        from jax.sharding import Mesh

        from unionml_tpu.parallel.mesh import AXIS_ORDER

        devices = list(jax.devices())
        if replicas > len(devices):
            logger.warning(
                f"ReplicaSet: {replicas} replicas over {len(devices)} devices — replicas "
                "beyond the device count time-slice chips round-robin"
            )
        shape = (1,) * len(AXIS_ORDER)
        return [
            Mesh(np.asarray([devices[i % len(devices)]]).reshape(shape), AXIS_ORDER)
            for i in range(replicas)
        ]

    @classmethod
    def from_generator(
        cls, generator: Any, *, replicas: Optional[int] = None, **engine_kwargs: Any
    ) -> "ReplicaSet":
        """Re-host an existing Generator's weights as a replica set (the
        ``ContinuousBatcher`` delegation path). Params are re-placed onto each
        submesh — an fsdp-sharded tree is gathered per replica, paid once at
        construction. A pre-QUANTIZED Generator (``quantize="int8"``, by kwarg
        or the serve-wide ``UNIONML_TPU_QUANTIZE`` export) replicates too: its
        int8 tree is dequantized back to the param dtype once here and each
        replica re-quantizes its own placement — symmetric per-channel int8 is
        an exact round trip (dequantize then quantize reproduces the identical
        ``q``/``scale`` planes), so every replica serves bit-identical weights
        to the original engine."""
        params = generator.params
        quantize = getattr(generator, "quantize", None)
        if quantize is not None:
            from unionml_tpu.ops.quant import dequantize_tree

            mcfg = getattr(generator.module, "config", None)
            param_dtype = getattr(mcfg, "param_dtype", None) or getattr(mcfg, "dtype", None)
            params = dequantize_tree(params, dtype=param_dtype or "float32")
        return cls.build(
            generator.module,
            params,
            generator.config,
            mesh=generator.mesh,
            partition_rules=getattr(generator, "partition_rules", None),
            quantize=quantize,
            replicas=replicas,
            **engine_kwargs,
        )

    # ------------------------------------------------------------------ public API

    @property
    def replicas(self) -> int:
        with self._lock:
            return len(self._batchers)

    @property
    def batchers(self) -> "Tuple[Any, ...]":
        """The per-replica engines (read-only view; benchmarks introspect it)."""
        with self._lock:
            return tuple(self._batchers)

    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int] = None,
        constraint: Optional[int] = None,
        deadline: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
        export_handoff: bool = False,
        logprobs: bool = False,
    ) -> "Iterator[np.ndarray]":
        """Route a prompt to the least-loaded replica (prefix affinity
        permitting) and return its engine's token stream. Sheds with
        :class:`DeadlineExceeded` if the deadline already expired, and with
        :class:`QueueFullError` only when every replica's waiting queue is
        full — the scheduler's order is walked so one full replica never turns
        away work its siblings could take.

        With roles configured (docs/serving.md "Disaggregated and elastic
        serving"), a prompt at least ``prefill_threshold`` tokens long takes
        the DISAGGREGATED path instead: its prefill runs on a prefill-role
        replica and at admission-complete the finished KV blocks hand off to
        a decode replica — the stream's tokens (the first included) are
        bit-identical to a single mixed replica serving it, but resident
        decode streams never stall behind the prefill. A warm multi-turn
        prompt whose radix-cached run on a decode replica already covers all
        but a sub-threshold suffix skips the handoff and admits there
        directly (the cache IS the prefill)."""
        req_trace = current_trace()
        if expired(deadline):
            with self._lock:
                self.shed_deadline += 1
            if req_trace is not None:
                req_trace.event("engine.shed_deadline", phase="routing")
            raise DeadlineExceeded("deadline expired before the prompt was routed to a replica")
        with self._lock:
            batchers = list(self._batchers)
            roles = list(self._roles)
        if export_handoff:
            # the multi-host fleet's prefill leg (serving/cluster.py): run ONLY
            # the prefill on this host's best-suited replica and hand the
            # block-native payload back on the stream's ``handoff`` attribute —
            # the coordinator ships it to another HOST's import_handoff
            return self._submit_export(
                batchers, roles, prompt,
                max_new_tokens=max_new_tokens, constraint=constraint, deadline=deadline,
                tenant=tenant, priority=priority,
            )
        if any(role == "prefill" for role in roles) and not logprobs:
            # logprobs requests skip the handoff pair (the logprob column does
            # not ride the KV payload) and admit directly on a decode/mixed
            # replica through the classic walk below
            stream = self._submit_disaggregated(
                batchers, roles, prompt,
                max_new_tokens=max_new_tokens, constraint=constraint, deadline=deadline,
                req_trace=req_trace, tenant=tenant, priority=priority,
            )
            if stream is not None:
                return stream
        return self._submit_routed(
            batchers, roles, prompt,
            max_new_tokens=max_new_tokens, constraint=constraint, deadline=deadline,
            req_trace=req_trace, tenant=tenant, priority=priority, logprobs=logprobs,
        )

    def _submit_routed(
        self,
        batchers: "List[Any]",
        roles: "List[str]",
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int],
        constraint: Optional[int],
        deadline: Optional[float],
        req_trace: Any,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
        logprobs: bool = False,
    ) -> "Iterator[np.ndarray]":
        """The classic least-loaded walk (PR 2), over a resize-stable snapshot.
        In a role-split fleet, prefill-role replicas are deprioritized — they
        still appear in the walk so a fleet whose decode tier is saturated
        degrades to using them rather than shedding."""
        # the routing tenant: the explicit kwarg, else the contextvar the HTTP
        # layer bound — resolved HERE (not just in the engine) because tenant
        # session affinity is a routing concern
        route_tenant = tenant if tenant is not None else current_tenant()
        loads = [batcher.load() for batcher in batchers]
        # actual per-replica cached-prefix lengths (the radix-tree probe) when
        # any engine runs a prefix cache; None keeps the LRU token-key fallback
        cached = None
        if any(getattr(b, "_radix", None) is not None for b in batchers):
            cached = [
                int(getattr(b, "cached_prefix_tokens", lambda _p: 0)(prompt))
                for b in batchers
            ]
        # per-replica SLO breach flags (cached health evaluations — cheap per
        # decision): a breaching replica is routed around, not routed to
        breaching = None
        if any(callable(getattr(b, "health", None)) for b in batchers):
            breaching = [
                callable(getattr(b, "health", None)) and b.health().get("state") == "breach"
                for b in batchers
            ]
        deprioritized = (
            [role == "prefill" for role in roles]
            if any(role == "prefill" for role in roles)
            else None
        )
        order, affinity_head = self._scheduler.order(
            loads, prompt, cached, breaching, deprioritized, tenant=route_tenant
        )
        if breaching is not None and any(breaching):
            # pure load order would have picked this replica; health demoted it
            pure_head = min(range(len(loads)), key=lambda i: (loads[i], i))
            if breaching[pure_head] and order and order[0] != pure_head:
                with self._lock:
                    self.breach_avoided += 1
        last_exc: Optional[QueueFullError] = None
        for replica in order:
            if req_trace is not None:
                # which replica, and the load it saw — recorded per ATTEMPT, so
                # a full replica's fall-through is visible on the timeline
                req_trace.event(
                    "engine.routed", replica=replica, load=round(loads[replica], 3),
                    affinity=bool(affinity_head) and replica == order[0],
                    breaching=bool(breaching[replica]) if breaching is not None else False,
                )
            try:
                stream = batchers[replica].submit(
                    prompt, max_new_tokens=max_new_tokens, constraint=constraint,
                    deadline=deadline, tenant=tenant, priority=priority,
                    logprobs=logprobs,
                )
            except TenantThrottled:
                # every replica shares the same tenant registry, so walking the
                # fleet could only re-shed — propagate the bucket's Retry-After
                # (and the tenant-limit shed reason) to the HTTP layer intact
                raise
            except QueueFullError as exc:
                last_exc = exc
                continue
            self._scheduler.note(
                replica, prompt,
                affinity=affinity_head if replica == order[0] else False,
                tenant=route_tenant,
            )
            return stream
        with self._lock:
            self.shed_queue_full += 1
        if req_trace is not None:
            req_trace.event("engine.shed_queue_full", replicas=len(batchers))
        raise QueueFullError(
            f"all {len(batchers)} replicas' waiting queues are full"
        ) from last_exc

    # ------------------------------------------------------------- disaggregation

    def _submit_export(
        self,
        batchers: "List[Any]",
        roles: "List[str]",
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int],
        constraint: Optional[int],
        deadline: Optional[float],
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> "Iterator[np.ndarray]":
        """Run an EXPORT prefill on this fleet: prefill-role replicas first,
        then least-loaded, with the usual full-queue fall-through. The
        returned stream carries the handoff payload for a DIFFERENT host's
        decode fleet — this fleet never takes the residency."""
        rank = {"prefill": 0, "mixed": 1, "decode": 2}
        loads = [batcher.load() for batcher in batchers]
        order = sorted(
            range(len(batchers)), key=lambda i: (rank.get(roles[i], 1), loads[i], i)
        )
        last_exc: Optional[QueueFullError] = None
        for replica in order:
            try:
                stream = batchers[replica].submit(
                    prompt, max_new_tokens=max_new_tokens, constraint=constraint,
                    deadline=deadline, export_handoff=True,
                    tenant=tenant, priority=priority,
                )
            except TenantThrottled:
                raise
            except QueueFullError as exc:
                last_exc = exc
                continue
            self._scheduler.note(replica, prompt)
            return stream
        with self._lock:
            self.shed_queue_full += 1
        raise QueueFullError(
            f"all {len(batchers)} replicas' waiting queues are full"
        ) from last_exc

    def import_handoff(self, payload: Dict[str, Any]) -> "Iterator[np.ndarray]":
        """Adopt another HOST's exported prefill onto this fleet's best decode
        replica (the cluster coordinator's cross-host landing path; the same
        decode → mixed → prefill fallback order as the in-fleet relay)."""
        return self._import_payload(payload, current_trace())

    def _submit_disaggregated(
        self,
        batchers: "List[Any]",
        roles: "List[str]",
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int],
        constraint: Optional[int],
        deadline: Optional[float],
        req_trace: Any,
        tenant: Optional[str] = None,
        priority: Optional[int] = None,
    ) -> "Optional[Iterator[np.ndarray]]":
        """The prefill→decode handoff path; None = not applicable (short
        prompt, no viable pair, or every prefill replica's queue full — the
        caller falls back to the classic walk, so disaggregation can only
        redirect work, never shed it)."""
        prefills = [i for i, role in enumerate(roles) if role == "prefill"]
        targets = [i for i, role in enumerate(roles) if role == "decode"] or [
            i for i, role in enumerate(roles) if role == "mixed"
        ]
        if not prefills or not targets or len(prompt) < self._prefill_threshold:
            return None
        loads = [batcher.load() for batcher in batchers]
        # warm multi-turn shortcut: a decode replica that already caches all
        # but a sub-threshold suffix of this prompt admits it directly — its
        # radix gather replaces the prefill a prefill replica would re-run
        warm = [
            (int(getattr(batchers[t], "cached_prefix_tokens", lambda _p: 0)(prompt)), -loads[t], t)
            for t in targets
            if getattr(batchers[t], "_radix", None) is not None
        ]
        if warm:
            cached_len, _, warm_t = max(warm)
            # direct-admit when the cache already covers MORE than half the
            # prompt (or the uncached suffix is sub-threshold): the residual
            # prefill there is cheaper than re-running the whole prompt on
            # the prefill tier plus a cross-replica transfer
            suffix = len(prompt) - cached_len
            if cached_len > 0 and suffix < max(self._prefill_threshold, (len(prompt) + 1) // 2):
                try:
                    stream = batchers[warm_t].submit(
                        prompt, max_new_tokens=max_new_tokens,
                        constraint=constraint, deadline=deadline,
                        tenant=tenant, priority=priority,
                    )
                except TenantThrottled:
                    raise  # the bucket sheds fleet-wide; see _submit_routed
                except QueueFullError:
                    pass
                else:
                    if req_trace is not None:
                        req_trace.event(
                            "engine.routed", replica=warm_t, load=round(loads[warm_t], 3),
                            role=roles[warm_t], cached=cached_len,
                        )
                    self._scheduler.note(
                        warm_t, prompt,
                        tenant=tenant if tenant is not None else current_tenant(),
                    )
                    with self._lock:
                        self.handoff_shortcuts += 1
                    return stream
        for p in sorted(prefills, key=lambda i: (loads[i], i)):
            if req_trace is not None:
                req_trace.event(
                    "engine.routed", replica=p, load=round(loads[p], 3), role="prefill",
                )
            try:
                pstream = batchers[p].submit(
                    prompt, max_new_tokens=max_new_tokens, constraint=constraint,
                    deadline=deadline, export_handoff=True,
                    tenant=tenant, priority=priority,
                )
            except TenantThrottled:
                raise
            except QueueFullError:
                continue
            self._scheduler.note(p, prompt)
            with self._lock:
                self.handoff_routes += 1
            return self._relay(pstream, req_trace)
        return None  # every prefill replica full: degrade to the classic walk

    def _relay(self, pstream: Any, req_trace: Any) -> "Iterator[np.ndarray]":
        """Stitch the prefill replica's one-token export stream and the decode
        replica's resident stream into one consumer-facing iterator. Closing
        the relay (client disconnect) closes whichever leg is active, so the
        producer never decodes to a dead connection."""
        active = pstream
        try:
            for item in pstream:
                yield item
            payload = pstream.handoff
            if payload is None:
                return  # finished outright at the prompt-sampled token
            dstream = self._import_payload(payload, req_trace)
            active = dstream
            for item in dstream:
                yield item
        finally:
            try:
                active.close()
            except Exception:  # pragma: no cover - defensive
                pass

    def _import_payload(self, payload: Dict[str, Any], req_trace: Any) -> Any:
        """Land an exported prefill on the best live decode replica (decode →
        mixed → prefill fallback order; quiescing/closed replicas are walked
        past, so a mid-relay resize re-targets instead of failing)."""
        with self._lock:
            batchers = list(self._batchers)
            roles = list(self._roles)
        rank = {"decode": 0, "mixed": 1, "prefill": 2}
        loads = [batcher.load() for batcher in batchers]
        order = sorted(
            range(len(batchers)), key=lambda i: (rank.get(roles[i], 1), loads[i], i)
        )
        last_exc: Optional[BaseException] = None
        for t in order:
            try:
                stream = batchers[t].import_handoff(payload)
            except (QueueFullError, RuntimeError) as exc:
                last_exc = exc
                continue
            if req_trace is not None:
                req_trace.event(
                    "engine.routed", replica=t, load=round(loads[t], 3), role=roles[t],
                    handoff=True,
                )
            # the DECODE replica is where the tenant's session KV ends up: the
            # tenant-affinity map records it, not the prefill leg
            self._scheduler.note(t, payload.get("prompt"), tenant=payload.get("tenant"))
            return stream
        raise RuntimeError(
            f"no replica of {len(batchers)} could adopt the handed-off prefill"
        ) from last_exc

    def warmup(self) -> None:
        """Resolve every replica's admission/prefill/decode programs,
        concurrently — replicas own disjoint engines (and usually disjoint
        devices), so their compile walls overlap instead of stacking. With
        the AOT store armed (``aot=`` / ``UNIONML_TPU_AOT_PRELOAD``) each
        replica preloads serialized executables keyed to its own submesh —
        a restarted server with the same fleet layout warms load-bound."""
        from concurrent.futures import ThreadPoolExecutor

        batchers = self.batchers
        with ThreadPoolExecutor(max_workers=len(batchers)) as pool:
            # list() propagates the first failure instead of dropping it
            list(pool.map(lambda batcher: batcher.warmup(), batchers))

    def load(self) -> float:
        """Aggregate token-weighted load (the signal a layer above a fleet of
        ReplicaSets would schedule on, mirroring the engine's own)."""
        return sum(batcher.load() for batcher in self.batchers)

    def cached_prefix_tokens(self, prompt: Sequence[int]) -> int:
        """Longest radix-cached run of ``prompt`` across this fleet's replicas
        — the per-HOST quantity the cluster coordinator's fleet-global prefix
        routing compares (serving/cluster.py). 0 with no prefix caches."""
        best = 0
        for batcher in self.batchers:
            probe = getattr(batcher, "cached_prefix_tokens", None)
            if callable(probe):
                best = max(best, int(probe(prompt)))
        return best

    def health(self) -> Dict[str, Any]:
        """Fleet health (observability/health.py): mean + worst per-replica
        scores and the worst SLO state — the ``GET /healthz`` body."""
        from unionml_tpu.observability.health import fleet_health

        return fleet_health(self)

    def configure_slo(self, config: Any, replica: Optional[int] = None) -> None:
        """Swap SLO targets on every replica (or just ``replica`` — per-role
        targets for heterogeneous fleets) at runtime."""
        batchers = self.batchers
        targets = batchers if replica is None else [batchers[replica]]
        for batcher in targets:
            batcher.configure_slo(config)

    # ------------------------------------------------------------------ elasticity

    @property
    def roles(self) -> "List[str]":
        """Per-replica roles (``prefill``/``decode``/``mixed``), index-aligned
        with :attr:`batchers`."""
        with self._lock:
            return list(self._roles)

    def scale_to(self, n: int, *, role: Optional[str] = None, timeout: float = 120.0) -> int:
        """Resize the fleet to ``n`` replicas at runtime, returning the new
        count. Scale-UP places the construction template's params onto a
        spare submesh (or, mesh-less, the next device round-robin), warms the
        new engine up, and only then joins it to the scheduler — the first
        routed request never pays a cold compile. With the AOT store armed
        the warmup itself preloads serialized executables keyed to the new
        replica's submesh: a submesh the store has seen (an earlier scale-up,
        a previous process with the same fleet layout) joins without a single
        fresh XLA trace, so autoscaler oscillation costs milliseconds, not
        compile walls. ``role`` tags the added
        replicas (default: ``decode`` in a role-split fleet, ``mixed``
        otherwise). Scale-DOWN drains the TAIL replica with PR 1's machinery:
        it is unrouted and quiesced first (new submits bounce to siblings),
        residents and already-queued work finish within ``timeout``, then the
        engine closes and its submesh returns to the spare pool — zero
        in-flight streams lost. Serialized against the autoscaler; safe from
        any thread."""
        if n < 1:
            raise ValueError("a fleet cannot scale below 1 replica")
        if role is not None and role not in REPLICA_ROLES:
            raise ValueError(f"role must be one of {REPLICA_ROLES}, got {role!r}")
        with self._scale_lock:
            while True:
                with self._lock:
                    cur = len(self._batchers)
                if cur == n:
                    return n
                if n > cur:
                    self._add_replica(role)
                    with self._lock:
                        self.scaled_up += 1
                else:
                    self._remove_replica(timeout)
                    with self._lock:
                        self.scaled_down += 1

    def spare_capacity(self) -> int:
        """Replicas :meth:`scale_to` could still add: spare submeshes for a
        dp-mesh fleet, unbounded (-1 reported as a large sentinel is avoided —
        the visible device count) for a mesh-less one, 0 when no construction
        template was retained."""
        with self._lock:
            template = self._scale_template
            if template is None:
                return 0
            if template["meshless"]:
                import jax

                return len(jax.devices())  # round-robin: always placeable
            return len(template["spares"])

    def _add_replica(self, role: Optional[str]) -> None:
        """Build, warm, and join one replica (the _scale_lock holder)."""
        from unionml_tpu.models.generate import Generator

        with self._lock:
            template = self._scale_template
            if template is None:
                raise RuntimeError(
                    "scale-up needs the construction template a ReplicaSet.build()/"
                    "from_generator() fleet retains; this set was built from "
                    "pre-made generators/engines"
                )
            index = len(self._batchers)
            has_roles = any(r != "mixed" for r in self._roles)
            if template["spares"]:
                mesh = template["spares"].pop(0)
            elif template["meshless"]:
                mesh = self._single_device_meshes(index + 1)[index]
            else:
                raise RuntimeError(
                    "no spare submesh to place a new replica on (the dp mesh is fully "
                    "occupied); build with fewer initial replicas to keep headroom"
                )
        resolved = role or ("decode" if has_roles else "mixed")
        try:
            gen = Generator(
                template["module"], template["params"], template["config"],
                mesh=mesh, partition_rules=template["partition_rules"],
                quantize=template["quantize"],
            )
            engine = self._new_engine(gen, resolved if (has_roles or role) else None)
            # warm BEFORE joining the scheduler: the replica's first routed
            # request must never pay the cold XLA compile (ROADMAP item 5's
            # concern, held to at resize time)
            engine.warmup()
        except BaseException:
            with self._lock:
                if self._scale_template is template and mesh is not None and not template["meshless"]:
                    template["spares"].insert(0, mesh)
            raise
        with self._lock:
            self._batchers.append(engine)
            self._roles.append(resolved)
            self._replica_meshes.append(mesh)
            self._scheduler.resize(len(self._batchers))
        logger.info(f"replica {index} joined the fleet (role={resolved})")

    def _remove_replica(self, timeout: float) -> None:
        """Unroute, drain, and close the tail replica (the _scale_lock
        holder). The tail is the removal point so surviving replicas keep
        their scheduler indexes (and telemetry) stable — and because role
        expansion orders prefill first, the capacity tier drains before the
        prefill tier."""
        with self._lock:
            if len(self._batchers) <= 1:
                raise ValueError("a fleet cannot scale below 1 replica")
            engine = self._batchers.pop()
            role = self._roles.pop()
            mesh = self._replica_meshes.pop()
            self._scheduler.resize(len(self._batchers))
            template = self._scale_template
        # quiesce BEFORE draining: a routing snapshot taken just before the
        # pop may still hold this engine — its submit now sheds QueueFullError
        # and the scheduler walk lands the request on a surviving sibling
        engine.quiesce()
        deadline = time.monotonic() + max(timeout, 0.0)
        while time.monotonic() < deadline:
            resident, waiting = engine.occupancy()
            if resident == 0 and waiting == 0:
                break
            time.sleep(0.01)
        else:
            resident, waiting = engine.occupancy()
            logger.warning(
                f"scale-down drain timed out with {resident} resident / {waiting} waiting "
                "streams; closing anyway (stragglers finish on the engine thread)"
            )
        engine.close(wait=True, timeout=max(deadline - time.monotonic(), 1.0))
        if template is not None and mesh is not None and not template["meshless"]:
            with self._lock:
                template["spares"].insert(0, mesh)
        logger.info(f"replica drained and left the fleet (role={role})")

    # ------------------------------------------------------------------ autoscaler

    def configure_autoscaler(
        self,
        *,
        high: float,
        low: float = 0.0,
        interval_s: float = 10.0,
        min_replicas: int = 1,
        max_replicas: int = 0,
        role: str = "decode",
    ) -> "ReplicaSet":
        """Arm (or retune) the autoscaler: every ``interval_s`` the loop reads
        the fleet's windowed pressure — per-replica token-weighted ``load()``,
        forced over the high watermark while any replica's SLO state is
        *breach* (PR 8's ``health()`` as the scale-up trigger) — and resizes
        one replica at a time: above ``high`` it adds a ``role`` replica (if
        spare capacity remains and ``max_replicas`` allows; 0 = capacity-
        bound), below ``low`` it drains one (never under ``min_replicas``;
        ``low=0`` disables scale-down). The loop thread is owned and joined
        by :meth:`close`."""
        if high <= 0:
            raise ValueError("high watermark must be > 0 (use close/False to disable)")
        if low < 0 or low >= high:
            raise ValueError("low watermark must be in [0, high)")
        if interval_s <= 0 or min_replicas < 1 or max_replicas < 0:
            raise ValueError("interval_s > 0, min_replicas >= 1, max_replicas >= 0 required")
        if role not in REPLICA_ROLES:
            raise ValueError(f"role must be one of {REPLICA_ROLES}, got {role!r}")
        with self._lock:
            self._autoscale = {
                "high": float(high), "low": float(low), "interval_s": float(interval_s),
                "min_replicas": int(min_replicas), "max_replicas": int(max_replicas),
                "role": role,
            }
            if self._autoscale_thread is None:
                self._autoscale_thread = threading.Thread(
                    target=self._autoscale_loop, daemon=True
                )
                self._autoscale_thread.start()
        return self

    def _autoscale_pressure(self) -> float:
        """The watermark quantity: mean per-replica token-weighted load,
        saturated past the high watermark while any replica breaches its SLO
        (latency burn means the fleet is undersized even if raw occupancy
        looks moderate). Overridable by tests and bespoke policies."""
        with self._lock:
            batchers = list(self._batchers)
            config = self._autoscale
        load = sum(batcher.load() for batcher in batchers) / max(len(batchers), 1)
        breaching = any(
            callable(getattr(b, "health", None)) and b.health().get("state") == "breach"
            for b in batchers
        )
        if breaching and config is not None:
            load = max(load, config["high"] + 1.0)
        return load

    def _autoscale_loop(self) -> None:
        while True:
            with self._lock:
                config = self._autoscale
            interval = config["interval_s"] if config is not None else 1.0
            if self._autoscale_stop.wait(interval):
                return
            try:
                self._autoscale_step()
            except Exception:  # pragma: no cover - the loop must survive
                logger.exception("autoscaler step failed")

    def _autoscale_step(self) -> None:
        with self._lock:
            config = self._autoscale
            n = len(self._batchers)
        if config is None:
            return
        pressure = self._autoscale_pressure()
        ceiling = config["max_replicas"] or (n + self.spare_capacity())
        if pressure > config["high"] and n < ceiling and self.spare_capacity() > 0:
            logger.info(
                f"autoscaler: pressure {pressure:.2f} > high {config['high']:.2f}; "
                f"scaling {n} -> {n + 1}"
            )
            self.scale_to(n + 1, role=config["role"])
        elif config["low"] > 0 and pressure < config["low"] and n > config["min_replicas"]:
            logger.info(
                f"autoscaler: pressure {pressure:.2f} < low {config['low']:.2f}; "
                f"scaling {n} -> {n - 1}"
            )
            self.scale_to(n - 1)

    def tenant_slo(self) -> "Dict[str, Any]":
        """Fleet-wide per-tenant SLO verdicts: the worst replica's entry per
        tenant (observability/health.merge_tenant_slo) — ``{}`` when no
        tenant carries per-tenant targets, so the section stays absent on
        target-less fleets."""
        from unionml_tpu.observability.health import merge_tenant_slo

        return merge_tenant_slo(list(self.batchers))

    def tenant_census(self) -> "Dict[str, Dict[str, int]]":
        """Fleet-wide live per-tenant stream counts (multi-tenant QoS,
        ``/debug/fleet``): each replica's bounded census summed — empty when
        no identified-tenant traffic is in flight."""
        census: "Dict[str, Dict[str, int]]" = {}
        for batcher in self.batchers:
            fn = getattr(batcher, "tenant_census", None)
            if not callable(fn):
                continue
            for tenant, counts in fn().items():
                entry = census.setdefault(tenant, {"resident": 0, "waiting": 0})
                for key, value in counts.items():
                    entry[key] = entry.get(key, 0) + int(value)
        return census

    def queued_prefill_tokens(self) -> int:
        """Fleet-wide prefill backlog in tokens (engines that predate the
        token accounting report 0)."""
        return sum(
            int(getattr(batcher, "queued_prefill_tokens", lambda: 0)())
            for batcher in self.batchers
        )

    def replica_loads(self) -> "List[Dict[str, Any]]":
        """Per-replica occupancy for live gauges: cheap (no full stats dict),
        evaluated at ``/metrics`` snapshot time."""
        with self._lock:
            snapshot = list(zip(self._batchers, self._roles))
        out = []
        for i, (batcher, role) in enumerate(snapshot):
            resident, waiting = batcher.occupancy()
            out.append(
                {
                    "replica": i,
                    "role": role,
                    "resident": resident,
                    "waiting": waiting,
                    "free_slots": max(int(getattr(batcher, "slots", 0)) - resident, 0),
                    "prefill_backlog_tokens": int(
                        getattr(batcher, "queued_prefill_tokens", lambda: 0)()
                    ),
                    "shed_queue_full": getattr(batcher, "shed_queue_full", 0),
                    "shed_deadline": getattr(batcher, "shed_deadline", 0),
                }
            )
        return out

    def stats(self) -> Dict[str, Any]:
        """Fleet snapshot for ``/metrics``: aggregates plus per-replica engine
        stats and the scheduler's routing telemetry."""
        with self._lock:
            batchers = list(self._batchers)
            roles = list(self._roles)
        per_replica = [batcher.stats() for batcher in batchers]

        def total(key: str) -> int:
            return sum(int(entry.get(key) or 0) for entry in per_replica)

        with self._lock:
            shed_deadline, shed_queue_full = self.shed_deadline, self.shed_queue_full
            breach_avoided = self.breach_avoided
            handoff_routes, handoff_shortcuts = self.handoff_routes, self.handoff_shortcuts
            scaled_up, scaled_down = self.scaled_up, self.scaled_down
            autoscale = dict(self._autoscale) if self._autoscale is not None else None
        # fleet health headline (per-replica detail rides per_replica's own
        # rates/slo sections): strip the replicas list — stats() must not
        # duplicate every engine's health payload
        fleet = {
            key: value
            for key, value in self.health().items()
            if key != "replicas"
        }
        def total_prefill(key: str) -> int:
            return sum(
                int((entry.get("prefill") or {}).get(key) or 0) for entry in per_replica
            )

        has_roles = any(role != "mixed" for role in roles)
        return {
            "replicas": len(batchers),
            "scheduler": self._scheduler.stats(),
            # disaggregated serving: role census, routing counters, and the
            # fleet-wide handoff totals (per-replica transfer latency rides
            # per_replica's own handoff sections) — present only in role-split
            # fleets, so symmetric fleets keep today's stats byte-for-byte
            **(
                {
                    "roles": {
                        role: sum(1 for r in roles if r == role)
                        for role in ("prefill", "decode", "mixed")
                    },
                    "handoffs": {
                        "routed": handoff_routes,
                        "shortcuts": handoff_shortcuts,
                        "exported": sum(
                            int((entry.get("handoff") or {}).get("exported") or 0)
                            for entry in per_replica
                        ),
                        "imported": sum(
                            int((entry.get("handoff") or {}).get("imported") or 0)
                            for entry in per_replica
                        ),
                    },
                }
                if has_roles
                else {}
            ),
            # elastic resize: lifetime scale events + remaining headroom, and
            # the armed watermarks (absent while the autoscaler is off)
            **(
                {
                    "resize": {
                        "scaled_up": scaled_up,
                        "scaled_down": scaled_down,
                        "spare_capacity": self.spare_capacity(),
                        **({"autoscaler": autoscale} if autoscale is not None else {}),
                    }
                }
                if (scaled_up or scaled_down or autoscale is not None)
                else {}
            ),
            "slots": total("slots"),
            "resident": total("resident"),
            "waiting": total("waiting"),
            "admitting": total("admitting"),
            "decode_dispatches": total("decode_dispatches"),
            "decoded_rows": total("decoded_rows"),
            # stall-free admission, fleet-wide: chunk counters + the token
            # backlog the token-weighted routing acts on (per-replica TTFT/TBT
            # percentiles stay under per_replica — percentiles don't sum)
            "prefill_chunks": total_prefill("chunks"),
            "prefill_backlog_tokens": total_prefill("backlog_tokens"),
            # fleet-wide radix prefix-cache totals (present only when at least
            # one replica runs the cache, so cache-off fleets keep today's
            # stats byte-for-byte; per-replica detail stays under per_replica)
            **(
                {
                    "prefix_cache": {
                        key: sum(
                            int((entry.get("prefix_cache") or {}).get(key) or 0)
                            for entry in per_replica
                        )
                        for key in ("hits", "misses", "tokens_avoided", "evictions",
                                    "cow_copies", "cached_blocks", "cached_bytes",
                                    "pinned_blocks")
                    }
                }
                if any("prefix_cache" in entry for entry in per_replica)
                else {}
            ),
            # fleet-wide AOT preload totals (present only when some replica
            # runs a program store — store-off fleets keep today's stats
            # byte-for-byte; per-replica load/compile latency windows stay
            # under per_replica, since percentiles don't sum)
            **(
                {
                    "aot": {
                        key: sum(
                            int((entry.get("aot") or {}).get(key) or 0)
                            for entry in per_replica
                        )
                        for key in ("programs_loaded", "programs_compiled",
                                    "programs_serialized", "load_failures",
                                    "serialize_failures")
                    }
                }
                if any("aot" in entry for entry in per_replica)
                else {}
            ),
            # fleet-wide multi-tenant QoS totals (present only when some
            # replica reports a tenancy section — QoS-off fleets keep today's
            # stats byte-for-byte; per-tenant buckets ride the app's registry)
            **(
                {
                    "tenancy": {
                        key: sum(
                            int((entry.get("tenancy") or {}).get(key) or 0)
                            for entry in per_replica
                        )
                        for key in ("shed_tenant_limit", "priority_preemptions")
                    }
                }
                if any("tenancy" in entry for entry in per_replica)
                else {}
            ),
            # fleet-wide per-tenant SLO verdicts (worst replica wins per
            # tenant); absent unless some replica tracks tenant targets —
            # per-replica detail stays under per_replica
            **(
                {"tenant_slo": self.tenant_slo()}
                if any("tenant_slo" in entry for entry in per_replica)
                else {}
            ),
            # fleet-level sheds (all replicas full / expired before routing) on
            # top of each engine's own counters
            "shed_queue_full": shed_queue_full + total("shed_queue_full"),
            "shed_deadline": shed_deadline + total("shed_deadline"),
            # fleet health score/state + how often routing walked around a
            # breaching replica (the observability→routing feedback, observable)
            "health": fleet,
            "breach_avoided": breach_avoided,
            "per_replica": per_replica,
        }

    def close(self, wait: bool = True, timeout: float = 120.0) -> None:
        """Drain every replica: stop the autoscaler loop (a resize must not
        race the shutdown), stop admissions fleet-wide (no stragglers
        re-routed into a replica that is about to close), then wait out the
        drains under one shared timeout."""
        self._autoscale_stop.set()
        thread = self._autoscale_thread
        if thread is not None:
            thread.join(timeout=10.0)
        with self._lock:
            batchers = list(self._batchers)
        for batcher in batchers:
            batcher.close(wait=False)
        if wait:
            deadline = time.monotonic() + timeout
            for batcher in batchers:
                batcher.close(wait=True, timeout=max(deadline - time.monotonic(), 0.0))
