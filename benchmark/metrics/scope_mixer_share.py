"""Own device time of the state-space and convolution mixers (``mixer``:
in/out projections, the causal convolution, the chunked scan and the
one-step update) over the device's busy time in the traced stretch of a
served cell, by the scope each operation was traced in
(``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("mixer",))
