"""Own device time of the operations whose path holds no group of the
vocabulary over the device's busy time in the traced stretch of a served
cell (``benchmark/opscopes.py``): the number that says the scopes have
rotted, as ``idle_unattributed_share`` is for the spans."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, (opscopes.UNSCOPED,))
