"""Device time per training step of the tile diagnostics: the operations
under the program's ``tile_metrics`` scope (the per-tile ``pulses``,
``gp_sq``, ``sp_err`` and ``prog_events`` that the tile configuration's
``metrics`` selects, and their ``tile/*`` aggregation), by
``bench/scopes.py``."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, ("tile_metrics",))
