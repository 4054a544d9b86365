"""The engine's own queue wait (``GenerationResult.timing.queue_wait_s``:
submit to the start of admission), median over the window's requests."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    waits = [1000.0 * r["timing"]["queue_wait_s"]
             for r in obs["stats"].window_requests(obs["records"])
             if r["ok"] and r["timing"]]
    return obs["stats"].percentile(waits, 50.0) if waits else None
