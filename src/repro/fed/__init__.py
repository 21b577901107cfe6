from .client import local_gradient, per_sample_sigma
from .faults import CHAOS_SPEC, FaultPlan, FaultSpec, RoundFaults
from .server import aggregate_gradients, ipw_mass, ipw_weights
from .rounds import (FEELConfig, FEELTrainer, ResilienceConfig,
                     RoundMetrics)
from .paper import paper_setup

__all__ = ["local_gradient", "per_sample_sigma", "aggregate_gradients",
           "ipw_mass", "ipw_weights",
           "FEELConfig", "FEELTrainer", "RoundMetrics",
           "ResilienceConfig", "FaultSpec", "FaultPlan", "RoundFaults",
           "CHAOS_SPEC", "paper_setup"]
