"""engine (serving/continuous.py): milliseconds an iteration of the window waited for its admissions' results before it
could paste them (the first token, its log-probability, an export's token and length: ``wait_s["first_token"]`` +
``["first_logprob"]`` + ``["export"]`` of the iteration records that start in the window, divided by their number) —
the second half of the fence (ROADMAP S3): the first of an admission's reads waits through its chunks, and the paste is
dispatched only after it. Nothing on a program whose records do not name their waits (the program's own spans, host
clock)."""

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
    kinds = [WAITS.index(kind) for kind in ("first_token", "first_logprob", "export")]
    return 1e3 * sum(r.wait_s[k] for r in iterations for k in kinds) / len(iterations)
