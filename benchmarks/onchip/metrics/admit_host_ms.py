"""Mean host milliseconds of an admission in the traced part:
``serve.admit`` plus ``serve.arm``, over the steps that admitted (the
program's step records)."""
import hostspans


def read(run):
    recs = hostspans.traced_records(run)
    if recs is None:
        return None
    v = [r.self_ns["serve.admit"] + r.self_ns.get("serve.arm", 0)
         for r in recs if "serve.admit" in r.self_ns]
    return None if not v else hostspans.MS * sum(v) / len(v)
