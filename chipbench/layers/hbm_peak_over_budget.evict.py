"""Staging: the highest `tempo_search_hbm_cache_bytes` ever stood since
the process started (`tempo_search_hbm_cache_peak_bytes`), over
`storage.search_batch_cache_bytes`: the configuration's override where
it has one (the rehearsal's), else the program's shipped default. At
most 1 + the groups in flight: the configuration's third guarantee. A
program without the high-water gauge gives None."""
from chipbench.lib import metric_sum


def budget(run):
    over = run["config"].get("yaml", {}).get("storage", {}).get(
        "search_batch_cache_bytes")
    if over:
        return float(over)
    try:
        from tempo_tpu.db.tempodb import TempoDBConfig

        return float(TempoDBConfig.search_batch_cache_bytes)
    except (ImportError, AttributeError):
        return None


def compute(run):
    peak = metric_sum(run["counters"]["after"],
                      "tempo_search_hbm_cache_peak_bytes")
    b = budget(run)
    return peak / b if peak and b else None
