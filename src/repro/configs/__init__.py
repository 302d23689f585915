"""Architecture registry: one config per assigned architecture (+ torr_edge).

``get(name)`` returns the full published config; ``get_smoke(name)`` returns
a reduced same-family config for CPU smoke tests.
"""
from .registry import ARCHS, SHAPES, get, get_smoke, input_specs, shape_for

__all__ = ["ARCHS", "SHAPES", "get", "get_smoke", "input_specs", "shape_for"]
from .torr_edge import (DEPLOYMENTS, deployment,  # noqa: E402,F401
                        rt_budget_s, torr_edge, torr_edge_no_reuse)

__all__ += ["DEPLOYMENTS", "deployment", "rt_budget_s", "torr_edge",
            "torr_edge_no_reuse"]
