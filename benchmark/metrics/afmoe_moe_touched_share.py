"""``moe_touched_share`` for the ``afmoe`` stack, whose configuration
states the held experts as the range ``experts_held`` of the router's
outputs: the engine's ``moe_experts_touched`` (held experts with at
least one row, summed over layers and steps) over held experts x
``moe_layer_steps``, window's end less window's start: the share of the
held experts' weights a step has to read. ``None`` for another model's
configuration and from an engine that counts no expert layer."""


def read(obs):
    if obs["kind"] == "train_job" or "experts_held" not in obs["cfg"]:
        return None
    before, after = obs["before"], obs["after"]
    steps = (after.get("moe_layer_steps", 0)
             - before.get("moe_layer_steps", 0))
    if steps <= 0:
        return None
    lo, hi = obs["cfg"]["experts_held"]
    return 100.0 * (after["moe_experts_touched"]
                    - before["moe_experts_touched"]) / ((hi - lo) * steps)
