"""Own device time of the expert layer (``moe``), forward and back: the
grouped products ``lfm2_expert_share`` times, and the pairs' movement
around them over the device's busy time in the traced stretch of a
training step, by the scope each operation was traced in
(``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, True, ("moe",))
