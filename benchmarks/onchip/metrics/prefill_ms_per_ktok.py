"""Device milliseconds of prefill programs per 1000 valid prompt tokens
prefilled in the traced window."""


def read(run):
    _, dev_s = run.program_time("prefill")
    tokens, _ = run.prefill_work()
    if dev_s <= 0 or tokens == 0:
        return None
    return 1e3 * dev_s / (tokens / 1e3)
