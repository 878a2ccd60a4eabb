"""The general traffic generator: turns a mix's data file and a seed into work.

A mix is ``perf/traffic/<name>.json``; nothing about a mix lives in code, and a
new mix of these kinds is a new data file. The mix names its generator
(``"generator": "generate"`` is this file) and ``perf/run.py`` finds
``perf/traffic/<generator>.py`` by that name, so an arrival process that this file
cannot express is a new file with the same three functions (``requests``,
``warmup_requests``, ``rows``) and edits nothing here. Two kinds exist:
``requests`` (a serving schedule: closed-loop clients or an open loop with due
times, optionally grouped into sessions that share a long prefix) and ``rows``
(training rows of token ids and a label).

The open loop's arrivals are *not* a Poisson process: the gaps between sessions
are the quantiles of the exponential distribution at ``(i + 0.5) / block``,
repeated in every block, so every block of ``block`` sessions takes the same
time (7.66 mean gaps at block 8: the mid-point quantiles' mean is 0.957) and no
gap exceeds ``-ln(0.5 / block)`` mean gaps (2.8 at block 8). It is an
exponential's set of gaps without its run-to-run variance.

Steadiness rule: the *set* of sizes and gaps is the same for every seed. Each
quantity is drawn as the quantiles of its distribution at ``(i + 0.5) / block``
over a block of consecutive items, and the seed only shuffles each block and
draws the token ids. Any run of a few hundred requests therefore carries the
same mix of lengths, in another order.

The open-loop schedule and its lag arithmetic follow
``unionml_tpu/workloads/replayer.py`` (due time = start + offset, latency from
the due time, lag = actual send - due) and the session shape follows
``scenarios.py`` (turns of one session share their history); both are copied
here, re-sized, so that a later change to the program cannot move the yardstick.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> Dict[str, Any]:
    with open(os.path.join(_HERE, f"{name}.json")) as f:
        return json.load(f)


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative integer
    return np.random.default_rng(np.random.SeedSequence([int(seed) & (2**63 - 1), stream]))


def quantile(dist: Mapping[str, Any], u: np.ndarray) -> np.ndarray:
    """The distribution's value at probabilities ``u`` (floats, before rounding)."""
    kind = dist["dist"]
    if kind == "uniform":
        out = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "exponential":
        out = -float(dist["mean"]) * np.log1p(-u)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        out = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        out = np.maximum(out, dist["min"])
    if "max" in dist:
        out = np.minimum(out, dist["max"])
    return out


def stratified(dist: Mapping[str, Any], n: int, block: int, rng: np.random.Generator, integer: bool) -> np.ndarray:
    """``n`` values: consecutive blocks of ``block`` each hold the distribution's
    quantiles at ``(i + 0.5) / block``, shuffled within the block by ``rng``."""
    u = (np.arange(block) + 0.5) / block
    values = quantile(dist, u)
    out = np.concatenate([rng.permutation(values) for _ in range(-(-n // block))])[:n]
    return np.rint(out).astype(np.int64) if integer else out


@dataclasses.dataclass
class Request:
    index: int
    prompt: List[int]
    max_tokens: int
    #: seconds after the window opens at which the request is due (open loop); None in a closed loop
    due_s: Optional[float] = None
    #: session id and position of this ask in it (None: no session)
    session: Optional[int] = None
    ask: int = 0
    #: prompt tokens shared with the session's earlier asks (a cache may skip them)
    shared_tokens: int = 0


def requests(mix: Mapping[str, Any], seed: int, vocab: int, seconds: float) -> List[Request]:
    """The schedule for one window of ``seconds``. Closed loop: a pool of
    requests that the clients take in order. Open loop: every request due before
    the window closes (plus none after), by due time."""
    block = int(mix.get("block", 64))
    # a mix may fix its schedule (sizes, gaps and their order) as a replayed trace does; the seed then draws the
    # token ids alone, so every seed offers the same load
    sched = int(mix.get("schedule_seed", seed))
    if mix["loop"] == "closed":
        n = int(mix["pool_per_s"] * seconds) + int(mix["clients"]) * 2
        arrivals = None
    else:
        rate = float(mix["rate_per_s"])
        per_session = int(mix["sessions"]["asks"]) if mix.get("sessions") else 1
        n_units = int(math.ceil(rate / per_session * seconds * 1.5)) + block
        gaps = stratified({"dist": "exponential", "mean": per_session / rate}, n_units, block, _rng(sched, 1), False)
        arrivals = np.cumsum(gaps) - gaps[0] * 0.5
        n = n_units
    ids = _rng(seed, 2)
    out: List[Request] = []
    if not mix.get("sessions"):
        prompts = stratified(mix["prompt_tokens"], n, block, _rng(sched, 3), True)
        outputs = stratified(mix["output_tokens"], n, block, _rng(sched, 4), True)
        for i in range(n):
            due = None if arrivals is None else float(arrivals[i])
            if due is not None and due >= seconds:
                break
            prompt = ids.integers(1, vocab, size=int(prompts[i])).tolist()
            out.append(Request(len(out), prompt, int(outputs[i]), due))
        return out
    spec = mix["sessions"]
    asks = int(spec["asks"])
    shared = stratified(spec["shared_tokens"], n, block, _rng(sched, 3), True)
    questions = stratified(mix["prompt_tokens"], n * asks, block, _rng(sched, 5), True)
    outputs = stratified(mix["output_tokens"], n * asks, block, _rng(sched, 4), True)
    ask_gaps = stratified(spec["gap_s"], n * asks, block, _rng(sched, 6), False)
    for s in range(n):
        start = float(arrivals[s])
        if start >= seconds:
            break
        document = ids.integers(1, vocab, size=int(shared[s])).tolist()
        due = start
        for a in range(asks):
            k = s * asks + a
            if a:
                due += float(ask_gaps[k])
            if due >= seconds:
                break
            question = ids.integers(1, vocab, size=int(questions[k])).tolist()
            out.append(Request(0, document + question, int(outputs[k]), due, s, a, len(document) if a else 0))
    out.sort(key=lambda r: r.due_s)
    for i, r in enumerate(out):
        r.index = i
    return out


def warmup_requests(mix: Mapping[str, Any], vocab: int, chunk: int) -> List[List[Request]]:
    """Waves of requests that touch every shape the mix can reach, sent before
    the window: the longest and the shortest prompt with the longest answer and,
    for sessions, a second ask of the same document (a cached-prefix admission).
    Token ids are fixed (not from the seed) and never collide with a timed prompt's
    first block in practice; each wave is sent only after the one before finished."""
    rng = np.random.default_rng(12345)
    # the decode program has one shape whatever the answer's length: two dispatches' worth of tokens warm it
    longest_out = min(int(mix["output_tokens"]["max"]), int(mix.get("warmup_output_tokens", 17)))
    p_min, p_max = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    draw = lambda n: rng.integers(1, vocab, size=n).tolist()  # noqa: E731
    if not mix.get("sessions"):
        first = [Request(0, draw(p_max), longest_out), Request(1, draw(p_min), longest_out)]
        return [first]
    s_max = int(mix["sessions"]["shared_tokens"]["max"])
    s_min = int(mix["sessions"]["shared_tokens"]["min"])
    doc_long, doc_short = draw(s_max), draw(s_min)
    first = [Request(0, doc_long + draw(p_max), longest_out, session=0), Request(1, doc_short + draw(p_min), 2, session=1)]
    second = [
        Request(2, doc_long + draw(p_max), longest_out, session=0, ask=1, shared_tokens=s_max),
        Request(3, doc_short + draw(p_min), 2, session=1, ask=1, shared_tokens=s_min),
    ]
    return [first, second]


def rows(mix: Mapping[str, Any], seed: int, vocab: int, n_rows: int, stream: int = 0) -> np.ndarray:
    """``[n_rows, seq + 1]`` int32 training rows: ``seq`` token ids (all real
    tokens, none padded) and the label in the last column; every row differs."""
    rng = _rng(seed, 100 + stream)
    seq = int(mix["seq"])
    data = rng.integers(1, vocab, size=(n_rows, seq + 1), dtype=np.int32)
    data[:, -1] = rng.integers(0, int(mix["label_classes"]), size=n_rows)
    return data
