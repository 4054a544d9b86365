"""Own device time of the EVA mixer (``eva``: its projections, the
aligned window's exact keys, the summaries' walk and the merge, the
pools' writes and the summary writer, ``Wo``) over the device's busy
time in the traced stretch of a served cell, by the scope each operation
was traced in (``benchmark/opscopes.py``, which logs the whole table of
groups and children once a run: ``eva/window``, ``eva/summaries``,
``eva/write`` under ``decode`` and ``admit``)."""

from benchmark import opscopes


def read(obs):
    return opscopes.share(obs, False, ("eva",))
