"""Programs the trainer's jitted step compiled inside the window: its
jit cache's size after the window less before. Should read 0."""


def read(obs):
    if obs["kind"] != "train_job":
        return None
    return obs["compiles_after"] - obs["compiles_before"]
