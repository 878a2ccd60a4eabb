"""front (serving/http.py, app.py, openai_api.py down to the engine's first emission): the client's time to the
first token from the time the request was due, 90th percentile over the window's requests (a failed one counts as
the time-out). Stands beside the end-to-end ``ttft_p95_ms``: with some 77 requests a window it is the highest
percentile with ten samples beyond it, and reads the plateau of the prompts that missed the prefix cache."""

import statistics


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or len(facts["in_window"]) < 10:
        return None
    worst = facts["timeout_s"]
    waits = [r.first - r.due if r.ok else worst for r in facts["in_window"]]
    return statistics.quantiles(waits, n=10, method="inclusive")[-1] * 1e3
