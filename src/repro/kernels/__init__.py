"""Pallas TPU kernels for the analog-training hot spots + jnp oracles.

Modules:
  analog_update.py — fused pulse update (eq. 2 + stochastic rounding + c2c),
                    from operand arrays or drawn in VMEM from seeds
  analog_matmul.py — IO-quantized crossbar MVM (paper Table 7 pipeline)
  sp_filter.py     — chopped-EMA SP filter (eq. 12) + telemetry reductions
  ops.py           — jit wrappers, padding, backend dispatch
  ref.py           — pure-jnp oracles (single source of truth for the math)
"""
from . import ops, ref  # noqa: F401
