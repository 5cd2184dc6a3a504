"""Fused LOTION-AdamW optimizer-step kernel (one HBM pass per leaf).

``ops.fused_opt_step_leaf`` is the public entry point; ``ref.py`` is the
pure-jnp oracle (the unfused update chain's math, leaf-local).
"""

from .ops import fused_opt_step_leaf, fused_opt_step_sharded
from .ref import opt_step_ref

__all__ = ["fused_opt_step_leaf", "fused_opt_step_sharded", "opt_step_ref"]
