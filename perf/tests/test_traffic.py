"""The generator: the same set of sizes for every seed, in another order; large seeds; due times."""

import collections

import pytest

from perf.traffic import generate


def test_same_sizes_every_seed():
    mix = generate.load_mix("chat_sat")
    a = generate.requests(mix, 1, 32768, 60.0)
    b = generate.requests(mix, 5_000_000_001, 32768, 60.0)
    assert len(a) == len(b) > 64
    sizes = lambda rs: collections.Counter((len(r.prompt), r.max_tokens) for r in rs[: mix["block"] * 4])  # noqa: E731
    assert sorted(len(r.prompt) for r in a[:256]) == sorted(len(r.prompt) for r in b[:256])
    assert sorted(r.max_tokens for r in a[:256]) == sorted(r.max_tokens for r in b[:256])
    assert [len(r.prompt) for r in a[:64]] != [len(r.prompt) for r in b[:64]]
    assert a[0].prompt != b[0].prompt and sizes(a)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi and 1 <= min(r.prompt) and max(r.prompt) < 32768 for r in a)
    assert generate.requests(mix, 1, 32768, 60.0)[3].prompt == a[3].prompt  # the same seed, the same inputs


def test_sessions_share_their_document_and_keep_the_rate():
    mix = generate.load_mix("docs")
    seconds = 60.0
    rs = generate.requests(mix, 7, 32768, seconds)
    assert all(0 <= r.due_s < seconds for r in rs) and [r.due_s for r in rs] == sorted(r.due_s for r in rs)
    assert abs(len(rs) / seconds - mix["rate_per_s"]) < 0.2 * mix["rate_per_s"]
    by_session = collections.defaultdict(list)
    for r in rs:
        by_session[r.session].append(r)
    full = [s for s in by_session.values() if len(s) == mix["sessions"]["asks"]]
    assert full
    for asks in full:
        shared = asks[1].shared_tokens
        assert 2048 <= shared <= 3072 and asks[0].shared_tokens == 0
        assert all(a.prompt[:shared] == asks[0].prompt[:shared] for a in asks)
        assert len({tuple(a.prompt[shared:]) for a in asks}) == len(asks)
    waves = generate.warmup_requests(mix, 32768, 256)
    assert len(waves) == 2 and waves[1][0].prompt[:3072] == waves[0][0].prompt[:3072]


def test_open_loop_gaps_are_stratified_not_poisson():
    """What the mix's file says of its arrivals: every block of 8 sessions takes 7.66 mean gaps, no gap is over
    2.8 mean gaps, and the schedule is the same for every seed (the seed draws the tokens alone)."""
    mix = generate.load_mix("docs")
    a, b = generate.requests(mix, 7, 32768, 120.0), generate.requests(mix, 8, 32768, 120.0)
    assert [(r.due_s, len(r.prompt), r.max_tokens) for r in a] == [(r.due_s, len(r.prompt), r.max_tokens) for r in b]
    assert a[0].prompt != b[0].prompt
    starts = sorted(r.due_s for r in a if r.ask == 0)
    mean_gap = mix["sessions"]["asks"] / mix["rate_per_s"]
    gaps = [y - x for x, y in zip(starts, starts[1:])]
    assert max(gaps) <= 2.8 * mean_gap and len(starts) > 2 * mix["block"]
    block = mix["block"]
    for k in (1, 2):  # gaps k*block .. (k+1)*block - 1: one whole block, in whatever order
        assert starts[(k + 1) * block - 1] - starts[k * block - 1] == pytest.approx(7.659 * mean_gap, rel=1e-3)


def test_a_mix_names_its_generator():
    import importlib

    for name in ("chat_sat", "docs", "sst2_rows"):
        module = importlib.import_module("perf.traffic." + generate.load_mix(name)["generator"])
        assert all(hasattr(module, f) for f in ("requests", "warmup_requests", "rows"))


def test_rows_differ():
    mix = generate.load_mix("sst2_rows")
    rows = generate.rows(mix, 3, 30522, 512)
    assert rows.shape == (512, 129) and set(rows[:, -1]) == {0, 1}
    assert len({r.tobytes() for r in rows}) == 512
    assert (generate.rows(mix, 3, 30522, 512) == rows).all() and not (generate.rows(mix, 4, 30522, 512) == rows).all()
