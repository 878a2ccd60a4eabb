"""The comparisons that decide ``correct``: numbers, each beside its limit.

A verdict is a list of ``(name, value, limit)``; a run is correct when every
value is at or under its limit and nothing is missing or non-finite. Limits live
in the cell's file (``limits``), set from chip readings as PERF.md records; a
limit of ``null`` there means "not set yet" and fails the run.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

Number = Tuple[str, float, Optional[float]]


def verdict(numbers: Sequence[Number]) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """(correct, {name: {"value": v, "limit": l}})."""
    ok = bool(numbers)
    table: Dict[str, Dict[str, Any]] = {}
    for name, value, limit in numbers:
        table[name] = {"value": value, "limit": limit}
        if limit is None or value is None or not math.isfinite(value) or value > limit:
            ok = False
    return ok, table


# ----------------------------------------------------------------------------- served tokens


def token_gaps(logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 when the served token is the argmax)."""
    logits = np.asarray(logits, np.float32)
    served = np.asarray(served, np.int64)
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def logprob_diffs(logits: np.ndarray, served: Sequence[int], served_logprobs: Sequence[float]) -> np.ndarray:
    """|served log-probability - reference log-softmax at the served token|."""
    logits = np.asarray(logits, np.float64)
    m = logits.max(axis=-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(axis=-1, keepdims=True))
    ref = logp[np.arange(len(served)), np.asarray(served, np.int64)]
    return np.abs(np.asarray(served_logprobs, np.float64) - ref)


def sample_requests(finished: Sequence[Mapping[str, Any]], k: int, seed: int) -> List[int]:
    """Indices into ``finished`` of ``k`` requests drawn from the seed: the
    longest (prompt + answer) always, then — where the traffic has sessions —
    one first ask and one later ask (a cached-prefix admission), then a draw."""
    if not finished:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & (2**63 - 1), 777]))
    order = list(rng.permutation(len(finished)))
    longest = max(range(len(finished)), key=lambda i: finished[i]["prompt_tokens"] + finished[i]["output_tokens"])
    picked = [longest]
    for want_later in (False, True):
        for i in order:
            if i not in picked and finished[i].get("session") is not None and bool(finished[i].get("ask")) == want_later:
                picked.append(i)
                break
    for i in order:
        if len(picked) >= k:
            break
        if i not in picked:
            picked.append(i)
    return picked[:k]


# ----------------------------------------------------------------------------- training


def leaf_norms(tree: Any) -> Dict[str, float]:
    """Euclidean norm of each leaf, keyed by its path."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(np.sqrt(np.sum(np.square(np.asarray(leaf, np.float64))))) for path, leaf in flat}


def worst_leaf_gap(program: Mapping[str, float], reference: Mapping[str, float],
                   skip: Sequence[str] = ()) -> Tuple[float, str]:
    """The widest gap between the program's and the reference's norm of a leaf,
    over the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    if set(program) != set(reference):
        return float("inf"), "leaves differ: " + ",".join(sorted(set(program) ^ set(reference))[:3])
    names = [n for n in reference if n not in skip]
    median = float(np.median([reference[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        gap = abs(program[n] - reference[n]) / max(reference[n], median, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def median_leaf_difference(program: Any, reference: Any) -> float:
    """Median over the leaves of ||program - reference|| / ||reference||: the rounding noise's size, which the
    gap between two norms averages away (noise without bias hardly moves a norm)."""
    import jax

    got = dict(jax.tree_util.tree_flatten_with_path(program)[0])
    want = dict(jax.tree_util.tree_flatten_with_path(reference)[0])
    if set(got) != set(want):
        return float("inf")
    ratios = []
    for path, leaf in want.items():
        r = np.asarray(leaf, np.float64)
        norm = float(np.sqrt(np.sum(np.square(r))))
        if norm > 0:
            ratios.append(float(np.sqrt(np.sum(np.square(np.asarray(got[path], np.float64) - r)))) / norm)
    return float(np.median(ratios)) if ratios else float("inf")


def idle_gradient_leaves(reference_grad_norms: Mapping[str, float]) -> List[str]:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: under Adam they move by round-off alone and are left out of the
    parameter-change comparison (by this rule, not by name)."""
    median = float(np.median(list(reference_grad_norms.values())))
    return [n for n, g in reference_grad_norms.items() if g < 1e-3 * median]
