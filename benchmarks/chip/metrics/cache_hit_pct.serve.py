"""``cache_hit_pct.serve``: requests answered by the result cache over
requests served (``TenantMetrics.cache_hit``)."""


def read(run):
    served = [r for r in run["record"].get("requests", ()) if "serve" in r]
    if not served:
        return None
    return 100.0 * sum(bool(r["serve"]["cache_hit"])
                       for r in served) / len(served)
