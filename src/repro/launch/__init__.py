"""Launchers: the batched toolchain sweep driver (`repro.launch.sweep`),
plus the language-model stack's mesh construction, jitted train/serve
steps and HLO analysis."""
from .sweep import SweepResult, config_grid, pareto_flags, run_sweep

__all__ = ["SweepResult", "config_grid", "pareto_flags", "run_sweep"]
