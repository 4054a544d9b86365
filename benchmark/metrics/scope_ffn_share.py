"""Own device time of the dense feed-forward (``ffn``) over the device's
busy time in the traced stretch of a served cell, by the scope each
operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("ffn",))
