"""The pulse-update kernel's share of its roofline: the least time the HBM
bytes E-RIDER's update needs per step could take at the chip's peak
bandwidth (``bench/counts.py`` ``update_bytes_per_step``; the update is
elementwise, so bytes and not operations bound it), over the kernel's
device time per step from the trace."""
import importlib.util
import os


def _kernel_seconds(rec):
    path = os.path.join(os.path.dirname(__file__), "pulse_kernel_ms.train.py")
    spec = importlib.util.spec_from_file_location("pulse_kernel_ms_train", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.kernel_seconds(rec)


def read(rec):
    s = _kernel_seconds(rec)
    if not s or not rec.get("steps"):
        return None
    ideal = rec["update_bytes_per_step"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * ideal / (s / rec["steps"])
