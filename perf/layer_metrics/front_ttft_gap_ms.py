"""front (serving/http.py, app.py, openai_api.py): what the front adds to the time to the first token on both sides
of the engine — HTTP parsing, the executor hand-over, the event stream. Mean over the client's records whose first
content event arrived in the window of (first event - the time the request was due), minus the mean of the engine's
own (first token - submitted) over the life-cycle records whose first token fell in the same window."""

from perf.layer_metrics import _common, _engine_log


def read(facts, trace, peak):
    inside = _engine_log.request_mean_ms(facts, "first_token", "submitted")
    seen = _common.first_tokens_between(facts["records"], facts["open_at"], facts["close_at"]) if inside is not None else []
    if not seen:
        return None
    return 1e3 * sum(r.first - r.due for r in seen) / len(seen) - inside
