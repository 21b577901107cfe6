"""Distribution & launch layer.

``python -m repro.launch.dryrun`` forces 512 host devices through
XLA_FLAGS in its ``main``, before jax initializes its backends;
importing the module changes nothing.
"""
from .mesh import (data_axes, data_size, make_host_mesh,
                   make_production_mesh, model_size)

__all__ = ["make_production_mesh", "make_host_mesh", "data_axes",
           "data_size", "model_size"]
