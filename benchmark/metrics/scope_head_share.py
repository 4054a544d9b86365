"""Own device time of the model's two ends (``embed`` + ``head``: the ids'
gather or one-hot projection, the output layer, sampling) over the
device's busy time in the traced stretch of a served cell, by the scope
each operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("embed", "head"))
