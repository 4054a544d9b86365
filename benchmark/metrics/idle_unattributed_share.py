"""Share of the traced stretch in which the device was idle between
programs and the stepper thread was in no span of the program: the
number that says the spans have rotted (``benchmark/hostspans.py``).
With the three ``idle_*_share`` metrics and the gaps inside programs
(``within_*``) it adds up to ``device_idle_share``."""

from benchmark import hostspans


def read(obs):
    return hostspans.share(obs, hostspans.UNATTRIBUTED)
