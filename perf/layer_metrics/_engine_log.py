"""Shared by the readers of the engine's own record (``unionml_tpu/observability/engine_log.py``; no metric of its
own): the iteration records that start inside the window and the life-cycle records of the requests whose first
token fell inside it, from the engine whose iterations overlap the window.

The window's edges (``facts["open_at"]``, ``facts["close_at"]``) and the records' stamps are all
``time.monotonic()`` of one process: the client's clock and the engine's are the same clock. The records outlive
the engine through the program's process-wide handle, so the readers run after the engine is freed. A program
without the handle (a commit before the record existed), or a window without a record, reads as nothing: every
function here returns ``None`` then and never raises.
"""

from __future__ import annotations

from typing import Any, List, Mapping, Optional, Tuple


def window(facts: Mapping[str, Any]) -> Optional[Tuple[List[Any], List[Any]]]:
    """``(iterations, requests)`` of the window, or nothing."""
    if facts.get("kind") != "serving":
        return None
    try:
        from unionml_tpu.observability import engine_log

        logs = engine_log.engine_logs()
    except (ImportError, AttributeError):
        return None
    t0, t1 = facts["open_at"], facts["close_at"]
    best: Optional[Tuple[List[Any], List[Any]]] = None
    for log in logs:
        iterations = [r for r in log.iteration_records() if t0 <= r.start < t1]
        if iterations and (best is None or len(iterations) > len(best[0])):
            requests = [r for r in log.request_records() if r.first_token is not None and t0 <= r.first_token < t1]
            best = (iterations, requests)
    return best


def phase_ms(facts: Mapping[str, Any], phase: Optional[str] = None) -> Optional[float]:
    """Milliseconds an iteration of the window spent in ``phase`` (in all six without one), on average."""
    found = window(facts)
    if found is None:
        return None
    from unionml_tpu.observability.engine_log import PHASES

    iterations = found[0]
    columns = range(len(PHASES)) if phase is None else [PHASES.index(phase)]
    return 1e3 * sum(r.phase_s[i] for r in iterations for i in columns) / len(iterations)


def request_mean_ms(facts: Mapping[str, Any], later: str, earlier: str) -> Optional[float]:
    """Mean of ``later - earlier`` (two stamps of the life-cycle record) over the requests whose first token
    fell in the window, in milliseconds."""
    found = window(facts)
    if found is None or not found[1]:
        return None
    gaps = [getattr(r, later) - getattr(r, earlier) for r in found[1]]
    return 1e3 * sum(gaps) / len(gaps)
