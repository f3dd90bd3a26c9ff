from repro.optim.adamw import adamw, mean_step
from repro.optim.lamb import lamb
from repro.optim.dpu import delayed_parameter_updates

__all__ = ["adamw", "mean_step", "lamb", "delayed_parameter_updates"]
