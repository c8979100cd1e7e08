"""sTiles core: structured sparse Cholesky factorization in JAX."""
from .structure import (ArrowheadStructure, TileGrid, measure_arrowhead,
                        tile_pattern_from_coo, banded_arrowhead_tile_pattern)
from .symbolic import SymbolicFactorization, Task, TaskType, symbolic_factorize
from .ctsf import BandedCTSF, TileMatrix
from .options import SolverOptions
from .ordering import (OrderingResult, PartitionPlan, adaptive_nd_ordering,
                       detect_partition_plan, partition_plan_from_ordering)
from .cholesky import (CholeskyFactor, factorize_tasklist, factorize_window,
                       factorize_window_batched)
from .tree_reduction import chunked_tree_sum, should_use_tree, tree_combine
from .solve import (backward_solve, backward_solve_many, forward_solve,
                    forward_solve_many, logdet, marginal_variances,
                    sample_gmrf, sample_gmrf_many, solve, solve_many,
                    solve_many_batched)
from .selinv import SelectedInverse, selected_inverse, selinv_batched
from .concurrent import concurrent_factorize, concurrent_selinv
from .gridpolicy import (GridBucketPolicy, assemble_rung_batch,
                         assemble_rung_rhs, embed_ctsf, embed_rhs,
                         padded_flop_overhead, restrict_factor, restrict_rhs,
                         restrict_selinv)
from .robustness import (STATUS_FAILED, STATUS_OK, STATUS_RECOVERED,
                         STATUS_SHED, FactorInfo, RegularizePolicy)

__all__ = [
    "ArrowheadStructure", "TileGrid", "measure_arrowhead",
    "tile_pattern_from_coo", "banded_arrowhead_tile_pattern",
    "SymbolicFactorization", "Task", "TaskType", "symbolic_factorize",
    "BandedCTSF", "TileMatrix",
    "SolverOptions",
    "OrderingResult", "PartitionPlan", "adaptive_nd_ordering",
    "detect_partition_plan", "partition_plan_from_ordering",
    "CholeskyFactor", "factorize_tasklist", "factorize_window",
    "factorize_window_batched",
    "chunked_tree_sum", "should_use_tree", "tree_combine",
    "backward_solve", "backward_solve_many", "forward_solve",
    "forward_solve_many", "logdet", "marginal_variances",
    "sample_gmrf", "sample_gmrf_many", "solve", "solve_many",
    "solve_many_batched",
    "SelectedInverse", "selected_inverse", "selinv_batched",
    "concurrent_factorize", "concurrent_selinv",
    "GridBucketPolicy", "assemble_rung_batch", "assemble_rung_rhs",
    "embed_ctsf", "embed_rhs", "padded_flop_overhead",
    "restrict_factor", "restrict_rhs", "restrict_selinv",
    "STATUS_FAILED", "STATUS_OK", "STATUS_RECOVERED", "STATUS_SHED",
    "FactorInfo", "RegularizePolicy",
]
