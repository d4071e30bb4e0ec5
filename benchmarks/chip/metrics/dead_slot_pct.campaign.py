"""``dead_slot_pct.campaign``: superchunk scan slots dispatched dead over
all slots dispatched, in the window's campaign shards: the counters
``sweep.dead_slots`` and ``sweep.slots`` of each ``sweep.split`` span (the
host's cut of a shard's range into per-variant segments) inside a
``campaign.shard`` span (program spans, ``program_spans.py``).  A program
without the ``sweep.split`` span reads as nothing."""
from program_spans import named, roots, under


def read(run):
    got = roots(run)
    if got is None or "cycles" not in run["record"]:
        return None
    _setup, window = got
    splits = [sp for r in window for s in named(r, "campaign.shard")
              for sp in under(r, s, "sweep.split")]
    slots = sum(sp["counters"].get("sweep.slots", 0) for sp in splits)
    if not slots:
        return None
    dead = sum(sp["counters"].get("sweep.dead_slots", 0) for sp in splits)
    return 100.0 * dead / slots
