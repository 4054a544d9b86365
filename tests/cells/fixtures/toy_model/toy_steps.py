"""Fixture reader: steps a training window ran."""


def read(obs):
    return obs["steps"] if obs["kind"] == "train_job" else None
