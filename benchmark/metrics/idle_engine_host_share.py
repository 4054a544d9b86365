"""Share of the traced stretch in which the device was idle between
programs while the stepper thread was in one of the engine step's host
spans (``serving.commit``, ``serving.tables``, ``serving.prompt_encode``,
``serving.prefill``, ``serving.decode_dispatch``, ``serving.token_sync``,
``serving.first_token_sync``, ``serving.round_end``, ``serving.round``'s
own time): ``benchmark/hostspans.py``."""

from benchmark import hostspans


def read(obs):
    return hostspans.share(obs, "engine step")
