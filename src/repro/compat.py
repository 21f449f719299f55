"""The JAX surface this repo touches, in one place.

* ``shard_map`` — ``jax.shard_map``; import it from here everywhere.
* ``Compiled.cost_analysis()`` — may return ``None`` for a program XLA
  has no cost model for; use :func:`cost_analysis_dict` to always get a
  flat dict.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

shard_map = jax.shard_map

__all__ = ["shard_map", "cost_analysis_dict"]


def cost_analysis_dict(compiled: Any) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when XLA reports
    none)."""
    return dict(compiled.cost_analysis() or {})
