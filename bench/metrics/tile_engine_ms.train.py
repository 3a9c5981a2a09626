"""Device time per training step of the tile engine without the pulse-update
kernel: the operations under the program's ``tile_begin`` (chopper draw,
Q-tilde sync), ``tile_effective`` (effective weights) and ``tile_update``
(the E-RIDER update and its diagnostics) scopes, less those under
``pulse_kernel``, by ``bench/scopes.py``. The kernel is read by
``pulse_kernel_ms.train``."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(
        rec, ("tile_begin", "tile_effective", "tile_update"), ("pulse_kernel",))
