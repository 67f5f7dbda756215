"""Share of the prompt tokens admitted in the window that the prefix
cache served (the engine's own counters over the window)."""


def read(run):
    hit = run.stats.get("prefix_hit_tokens", 0)
    total = hit + run.stats.get("prefix_miss_tokens", 0)
    return 100.0 * hit / total if total else None
