"""How late the load generator ran: send time less due time, 99th
percentile over every request of the run. A starved generator must not
read as a fast server."""


def read(obs):
    if obs["kind"] == "train_job" or obs.get("peaks") is None:
        return None
    late = obs["late_ms"]
    return obs["stats"].percentile(late, 99.0) if late else None
