"""Of the cache entries an EVA layer's attention reads, the share that
are summaries: the engine's ``eva_summary_entries_read`` over that plus
``eva_window_entries_read`` (what one layer's attention reads for every
dispatch's tables, decode steps and admission chunks alike), window's
end less window's start. 0 while no row has passed its first window;
``None`` from a program without the counters."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    if "eva_summary_entries_read" not in after:
        return None
    pooled, exact = (after[k] - before.get(k, 0) for k in (
        "eva_summary_entries_read", "eva_window_entries_read"))
    return 100.0 * pooled / (pooled + exact) if pooled + exact > 0 else None
