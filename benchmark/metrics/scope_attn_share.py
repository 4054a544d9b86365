"""Own device time of the attention group (``attn``: q/k/v projections and
head split, rotary, the attention program, the pool's writes, ``Wo``
with its gate and residual) over the device's busy time in the traced
stretch of a served cell, by the scope each operation was traced in
(``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("attn",))
