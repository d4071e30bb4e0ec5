"""``campaign_fixed_ms.campaign``: mean per cycle of the window of the
``explore`` and ``resume`` calls' time outside their ``campaign.shard``
spans: planning, loading and checking shards, the shared prep, the
writer's barrier, the merge and the report (program spans,
``program_spans.py``)."""
from program_spans import named, roots, seconds


def read(run):
    got = roots(run)
    rec = run["record"]
    if got is None or "cycles" not in rec:
        return None
    _setup, window = got
    fixed = sum(seconds(r) - sum(seconds(s)
                                 for s in named(r, "campaign.shard"))
                for r in window)
    return 1e3 * fixed / len(rec["cycles"])
