"""Declarative fault injection + chaos campaigns for the ABFT stack.

``model`` declares WHAT goes wrong (site x kind x timing), ``injectors``
makes it happen (bitcast bit-flips, sticky re-application, the kernel
accumulator hook), and ``campaign`` sweeps the grid and measures
detection / SDC / false-positive rates plus the guard's repair-tier
distribution.  The check path's own guard (periodic re-derivation of the
eq.-5 fold and the staged s_c) is part of the serving loop:
``repro.engine.selfcheck``.
"""
from repro.faults.campaign import (ExperimentResult, run_experiment,
                                   run_fault_campaign)
from repro.faults.injectors import FaultInjector, flip_bits
from repro.faults.model import (CHECK_PATH_SITES, CONSISTENT_SITES, KINDS,
                                SITES, TIMINGS, FaultModel, sweep_models)

__all__ = [
    "FaultModel", "sweep_models", "SITES", "KINDS", "TIMINGS",
    "CHECK_PATH_SITES", "CONSISTENT_SITES",
    "FaultInjector", "flip_bits",
    "run_fault_campaign", "run_experiment", "ExperimentResult",
]
