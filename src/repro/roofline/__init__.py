"""repro.roofline -- static HLO analysis + roofline cost terms.

  analysis  -- Roofline terms (compute/memory/collective seconds) from the
               compiled dry-run artifact
  hlo_stats -- call-graph walk over optimized HLO text: FLOPs, HBM bytes,
               collective bytes with while-loop trip multipliers
"""
from . import analysis, hlo_stats
from .analysis import Roofline
from .hlo_stats import Cost, analyze, analyze_by_shape

__all__ = ["analysis", "hlo_stats", "Roofline", "Cost", "analyze",
           "analyze_by_shape"]
