"""Device time per training step of the pulse-update kernel's inputs: the
operations under the program's ``pulse_operands`` scope (device parameters
regenerated from the tile seeds, the hash-noise draws, and the float32
increments the kernel reads), by ``bench/scopes.py``."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, ("pulse_operands",))
