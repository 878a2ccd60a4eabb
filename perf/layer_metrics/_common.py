"""Shared arithmetic of the per-layer readers (no metric of its own)."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional


def cached_estimate(request: Any, block: int) -> int:
    """Prompt tokens a prefix cache can skip for this request: a session's later
    asks share the document, in whole blocks (never the whole prompt)."""
    if not request.ask or not request.shared_tokens:
        return 0
    return min(request.shared_tokens // block * block, (len(request.prompt) - 1) // block * block)


def tokens_between(records: List[Any], t0: float, t1: float) -> List[int]:
    """For every output token that arrived in [t0, t1): the positions it attended to."""
    contexts: List[int] = []
    for r in records:
        produced = 0
        for when, n in r.arrivals:
            if t0 <= when < t1:
                base = len(r.request.prompt) + produced
                contexts.extend(range(base, base + n))
            produced += n
    return contexts


def first_tokens_between(records: List[Any], t0: float, t1: float) -> List[Any]:
    return [r for r in records if r.first is not None and t0 <= r.first < t1 and r.request.index >= 0]


def prefill_flops(facts: Mapping[str, Any]) -> float:
    """Model FLOPs of the prompts whose first token arrived in the window, the cached part of a session's later
    asks left out as far as the prefix cache's own counter says it was skipped (an expected hit that missed
    ran in full)."""
    from perf import work

    cfg = facts["config"]
    admitted = first_tokens_between(facts["records"], facts["open_at"], facts["close_at"])
    estimate = [cached_estimate(r.request, facts["block_size"]) for r in admitted]
    avoided = facts["after"].get("prefix_tokens_avoided", 0) - facts["before"].get("prefix_tokens_avoided", 0)
    scale = min(1.0, avoided / sum(estimate)) if sum(estimate) > 0 else 0.0
    return sum(work.prefill_flops(cfg, len(r.request.prompt), int(e * scale) if e else 0) for r, e in zip(admitted, estimate))


def program(trace: Optional[Mapping[str, Any]], names: List[str]) -> Optional[Dict[str, float]]:
    if not trace:
        return None
    calls = sum(trace["programs"].get(n, {}).get("calls", 0.0) for n in names)
    seconds = sum(trace["programs"].get(n, {}).get("seconds", 0.0) for n in names)
    return {"calls": calls, "seconds": seconds} if calls > 0 and seconds > 0 else None


def idle_share(facts: Mapping[str, Any], trace: Optional[Mapping[str, Any]], kind: str) -> Optional[float]:
    """1 - busy / traced window, in percent, for a cell of ``kind``; nothing where the trace saw no operation."""
    if facts.get("kind") != kind or not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
