"""Own device time of the optimizer's side of a step (``update`` +
``cast``: normalise, updater, subtract, ``grad_health``; the masters'
casts to the compute dtype, where XLA did not fuse them into a product)
over the device's busy time in the traced stretch of a training step, by
the scope each operation was traced in (``benchmark/opscopes.py``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, True, ("update", "cast"))
