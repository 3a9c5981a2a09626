"""Whole training step's share of the chip's bf16 peak: model FLOPs per
token (``bench/counts.py``) times the tokens trained in the traced window,
over the window's seconds and the peak of ``bench/peaks.json``."""


def read(rec):
    red = rec.get("trace")
    if not red or not rec.get("tokens"):
        return None
    rate = rec["flops_per_token"] * rec["tokens"] / red["window_s"]
    return 100.0 * rate / rec["peaks"]["bf16_flops_per_s"]
