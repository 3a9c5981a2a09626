"""Device time per training step of the pulse-update Pallas kernel
(``kernels/analog_update.py``). In a TPU trace the kernel's launches are
the ``XLA Ops`` events whose HLO is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``; the training step runs no other
Pallas kernel. The kernel has no stable name of its own yet (its launches
read ``%branch_0_fun.<n>``), so the call target is what identifies it."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def kernel_seconds(rec):
    from bench import trace

    red = rec.get("trace")
    return trace.op_seconds(red, KERNEL) if red else None


def read(rec):
    s = kernel_seconds(rec)
    if s is None or not rec.get("steps"):
        return None
    return 1e3 * s / rec["steps"]
