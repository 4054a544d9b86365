"""Admission (prefill) wall per thousand prompt tokens: the engine's
``timing.admission_s`` summed over the window's requests, over their
prompt tokens."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    done = [r for r in obs["stats"].window_requests(obs["records"])
            if r["ok"] and r["timing"]]
    tokens = sum(r["prompt_len"] for r in done)
    if not tokens:
        return None
    return 1000.0 * sum(r["timing"]["admission_s"] for r in done) / (
        tokens / 1000.0)
