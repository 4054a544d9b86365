"""Share of the held experts' weights a step has to read: the engine's
``moe_experts_touched`` (held experts with at least one row, summed
over layers and steps) over held experts x ``moe_layer_steps``,
window's end less window's start. ``None`` from an engine that counts
no expert layer."""


def read(obs):
    if obs["kind"] == "train_job":
        return None
    before, after = obs["before"], obs["after"]
    steps = (after.get("moe_layer_steps", 0)
             - before.get("moe_layer_steps", 0))
    if steps <= 0:
        return None
    held = obs["cfg"]["num_local_experts"]
    return 100.0 * (after["moe_experts_touched"]
                    - before["moe_experts_touched"]) / (held * steps)
