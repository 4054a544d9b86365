"""Own device time of the model's two ends (``embed`` + ``head``: the
embedding, the output layer's logits and the loss), forward and back
over the device's busy time in the traced stretch of a training step, by
the scope each operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, True, ("embed", "head"))
