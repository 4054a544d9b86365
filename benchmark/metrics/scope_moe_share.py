"""Own device time of the expert layer (``moe``: router, the pairs' sort
and gather, the grouped products, the scatter back and weighted sum, the
shared expert) over the device's busy time in the traced stretch of a
served cell, by the scope each operation was traced in
(``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("moe",))
