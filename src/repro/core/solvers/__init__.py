from repro.core.solvers.base import BoxQPResult, box_qp, kkt_residual, power_iteration_l
from repro.core.solvers.hinge import hinge_boxes, solve_hinge
from repro.core.solvers.least_squares import solve_krr_chol
from repro.core.solvers.quantile import quantile_boxes, solve_quantile
from repro.core.solvers.expectile import solve_expectile

__all__ = [
    "BoxQPResult",
    "box_qp",
    "kkt_residual",
    "power_iteration_l",
    "hinge_boxes",
    "solve_hinge",
    "solve_krr_chol",
    "quantile_boxes",
    "solve_quantile",
    "solve_expectile",
]
