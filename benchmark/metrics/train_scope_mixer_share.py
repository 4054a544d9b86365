"""Own device time of the gated short-convolution mixers (``mixer``:
``W_in`` / ``W_out`` and the taps), forward and back over the device's
busy time in the traced stretch of a training step, by the scope each
operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, True, ("mixer",))
