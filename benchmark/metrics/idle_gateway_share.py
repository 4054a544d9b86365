"""Share of the traced stretch in which the device was idle between
programs while the stepper thread was in one of the gateway's spans
(``gateway.lock_yield``, ``gateway.deliver``, ``gateway.idle_wait``):
``benchmark/hostspans.py``."""

from benchmark import hostspans


def read(obs):
    return hostspans.share(obs, "gateway")
