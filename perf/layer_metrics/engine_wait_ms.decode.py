"""engine (serving/continuous.py): milliseconds an iteration of the window waited for its decode dispatch's results (the
tokens, their log-probabilities, the done flags: ``wait_s["decode"]`` of the iteration records that start in the
window, divided by their number) — the first half of the fence (ROADMAP S3): what dispatching chunk N+1 before reading
chunk N would hide. With ``engine_wait_ms.admission`` it sums to ``engine_phase_ms.fetch`` where nothing speculates.
Nothing on a program whose records do not name their waits (the program's own spans, host clock)."""

from perf.layer_metrics import _engine_log


def read(facts, trace, peak):
    found = _engine_log.window(facts)
    if found is None:
        return None
    try:
        from unionml_tpu.observability.engine_log import WAITS
    except ImportError:
        return None
    iterations = found[0]
    if not all(hasattr(r, "wait_s") for r in iterations):
        return None
    return 1e3 * sum(r.wait_s[WAITS.index("decode")] for r in iterations) / len(iterations)
