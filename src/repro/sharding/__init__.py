"""Sharding: logical-axis rules, mesh context, partition specs."""

from .context import (current_mesh, data_axes, mesh_context, model_axis,
                      set_current_mesh)
from .rules import (logical_to_spec, make_rules, spec_tree)

__all__ = ["current_mesh", "set_current_mesh", "mesh_context",
           "data_axes", "model_axis", "logical_to_spec", "make_rules",
           "spec_tree"]
