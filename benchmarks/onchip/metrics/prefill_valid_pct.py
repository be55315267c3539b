"""Share of the prefill rows computed that held a prompt token (the
engine's ``prefill_stats``: valid_tokens over token_slots)."""


def read(run):
    slots = run.counter_delta("prefill", "token_slots")
    valid = run.counter_delta("prefill", "valid_tokens")
    return None if slots == 0 else 100.0 * valid / slots
