"""How long the engine held a request's first token before a streaming
client could see it: ``timing.first_delta_s`` (submit -> the first delta
handed to ``on_delta``) less ``timing.ttft_s`` (submit -> admission
fetched the first token), median over the window's requests. ``None``
from a program whose ``timing`` lacks ``first_delta_s``."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    held = [1000.0 * (r["timing"]["first_delta_s"]
                      - r["timing"]["ttft_s"])
            for r in obs["stats"].window_requests(obs["records"])
            if r["ok"] and r["timing"]
            and r["timing"].get("first_delta_s") is not None
            and r["timing"].get("ttft_s") is not None]
    return obs["stats"].percentile(held, 50.0) if held else None
