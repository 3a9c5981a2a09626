"""Device time per training step of the model's forward and backward pass:
the operations under the program's ``fwd_bwd`` scope (``train_step``'s
phase 2 in ``core/trainer.py``: ``value_and_grad`` of the loss, with the
microbatch scan and the gradient accumulation), by ``bench/scopes.py``."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, ("fwd_bwd",))
