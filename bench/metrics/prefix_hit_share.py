"""Share of prompt rows the prefix index served instead of a prefill, over
the window: shared / (prefilled + shared), from the engine's counters."""


def read(w):
    if w.get("kind") != "rollout":
        return None
    c = w["counters"]
    total = c["prefill_tokens"] + c["shared_prefill_tokens"]
    return 100.0 * c["shared_prefill_tokens"] / total if total else None
