"""engine (serving/prefix_cache.py): prompt tokens whose prefill the radix cache skipped / prompt tokens of
the requests admitted in the window, in percent (program counter over the generator's own count)."""


def read(facts, trace, peak):
    if facts.get("kind") != "serving" or "prefix_tokens_avoided" not in facts["after"]:
        return None
    avoided = facts["after"]["prefix_tokens_avoided"] - facts["before"]["prefix_tokens_avoided"]
    sent = sum(len(r.request.prompt) for r in facts["records"]
               if r.first is not None and facts["open_at"] <= r.first < facts["close_at"])
    if sent <= 0 or not any(r.request.shared_tokens for r in facts["records"]):
        return None
    return 100.0 * avoided / sent
