"""Time to the first token as the client sees it: due time to the first
streamed token, 90th percentile over the requests due in the window; a
failed or refused request counts as beyond it. A candidate end-to-end
metric that spread too widely from seed to seed to be judged by
(PERF.md), kept here so that it is still read."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    value = obs["stats"].ttft_percentile_ms(obs["records"], 90.0)
    return value if value != float("inf") else None
