"""``shard_finalize_ms.campaign``: mean time of the ``sweep.finalize``
spans inside each ``campaign.shard`` of the window's cycles: the
per-shard fetch, re-gather and assembly (program spans,
``program_spans.py``)."""
from program_spans import named, roots, seconds, under


def read(run):
    got = roots(run)
    if got is None or "cycles" not in run["record"]:
        return None
    _setup, window = got
    shards = [(r, s) for r in window for s in named(r, "campaign.shard")]
    if not shards:
        return None
    return 1e3 * sum(seconds(f) for r, s in shards
                     for f in under(r, s, "sweep.finalize")) / len(shards)
