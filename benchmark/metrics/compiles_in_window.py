"""Programs the engine compiled inside the window: the sum of
``compile_counts()`` at the window's end less at its start. Should
read 0."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    return obs["after"]["compiles"] - obs["before"]["compiles"]
