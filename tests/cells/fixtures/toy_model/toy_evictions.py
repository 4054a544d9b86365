"""Fixture reader: slots the engine vacated in the window
(``engine.stats["evicted"]``, a counter the harness never names: it
reaches this reader because ``obs`` carries the counters whole)."""


def read(obs):
    if obs["kind"] == "train_job" or "evicted" not in obs["after"]:
        return None
    return obs["after"]["evicted"] - obs["before"]["evicted"]
