"""Own device time of the attention group (``attn``), forward and back over
the device's busy time in the traced stretch of a training step, by the
scope each operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, True, ("attn",))
