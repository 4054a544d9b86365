"""What the gateway and the HTTP path add before the first token: the
client's time from sending to the first streamed token, less the
engine's own ``ttft_s`` of the same request; median over the requests
due in the window. (A submit waits for the stepper's lock, so a running
round shows here and not in the engine's queue wait.)"""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    over = [1000.0 * (r["token_times"][0] - r["sent"]
                      - r["timing"]["ttft_s"])
            for r in obs["stats"].window_requests(obs["records"])
            if r["ok"] and r["timing"] and r["timing"].get("ttft_s")
            is not None]
    return obs["stats"].percentile(over, 50.0) if over else None
