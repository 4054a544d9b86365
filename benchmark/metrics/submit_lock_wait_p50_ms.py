"""How long a request's handler waited before the engine had the
request: ``GenerationResult.timing.gateway_wait_s`` (the handler has the
parsed body -> ``engine.submit`` has returned: the wait for the
stepper's lock and the submit itself), median over the window's
requests. ``None`` from a program whose ``timing`` lacks the key."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    waits = [1000.0 * r["timing"]["gateway_wait_s"]
             for r in obs["stats"].window_requests(obs["records"])
             if r["ok"] and r["timing"]
             and r["timing"].get("gateway_wait_s") is not None]
    return obs["stats"].percentile(waits, 50.0) if waits else None
