"""Share of the traced stretch in which the device was idle between
programs while the stepper thread was in one of the scheduler's spans
(``serving.sweeps``, ``serving.admit`` less its children,
``serving.reserve``): ``benchmark/hostspans.py``."""

from benchmark import hostspans


def read(obs):
    return hostspans.share(obs, "scheduler")
