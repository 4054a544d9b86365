"""Own device time of the admission programs (phase ``admit``: ``prefill``,
``chunk_prefill``, ``scatter_row``, ``state_admit``, ``put_tok``), every
group of them over the device's busy time in the traced stretch of a
served cell, by the scope each operation was traced in
(``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, phase="admit")
