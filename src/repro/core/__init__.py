"""repro.core — the paper's contribution: recycled Krylov solvers for
sequences of related systems, pytree-native and pjit-shardable.

The public front doors are ``solve`` / ``solve_sequence`` / ``solve_batch``
driven by one ``SolveSpec`` and carrying a ``RecycleState`` (see
``core/api.py``).  The spec's method axis covers both workload families:
``cg``/``defcg`` for SPD systems and ``lsmr``/``deflsmr`` for regularized
least-squares (``core/lsmr.py``), all sharing the ``core/engine.py`` loop
harness.  ``cg``, ``defcg`` and ``lsmr`` are the solver bodies the front
doors run, exported for tests and for callers that manage no recycled
state.
"""

from repro.core.api import (
    BatchSolveResult,
    SequenceSolveResult,
    SolveReport,
    SolveResult,
    SolveSpec,
    make_preconditioner,
    solve,
    solve_batch,
    solve_batch_jit,
    solve_jit,
    solve_pool_step,
    solve_pool_step_jit,
    solve_sequence,
)
from repro.core.faults import FaultInjectingOperator, truncate_latest_checkpoint
from repro.core.lsmr import (
    lsmr,
    lsmr_jit,
    solve_sequence_lsmr,
    solve_sequence_lsmr_jit,
)
from repro.core.operators import (
    DenseMatrixOperator,
    GaussNewtonOperator,
    GGNOperator,
    KernelSystemOperator,
    LinearOperator,
    adjoint_matvec,
    apply_to_basis,
    from_callable,
    from_matrix,
    materialize,
)
from repro.core.preconditioners import (
    JacobiPreconditioner,
    NystromPreconditioner,
    WoodburyKernelPreconditioner,
    jacobi,
    kernel_nystrom_preconditioner,
    nystrom_preconditioner,
    randomized_nystrom,
)
from repro.core.recycle import (
    MAX_RECOVERY_RUNGS,
    RecycleState,
    SequenceResult,
    harmonic_ritz,
    harmonic_ritz_flat,
    random_orthonormal_basis,
)
from repro.core.solvers import (
    DEFAULT_WAW_JITTER,
    CGResult,
    RecycleData,
    SolveInfo,
    SolveStatus,
    cg,
    cholesky_solve,
    defcg,
    deflated_initial_guess,
)
from repro.core.strategies import (
    HarmonicRitz,
    MGeometryHarmonic,
    RecycleStrategy,
    WindowedRecombine,
)

__all__ = [
    "BatchSolveResult",
    "SequenceSolveResult",
    "SolveReport",
    "SolveResult",
    "SolveSpec",
    "make_preconditioner",
    "solve",
    "solve_batch",
    "solve_batch_jit",
    "solve_jit",
    "solve_pool_step",
    "solve_pool_step_jit",
    "solve_sequence",
    "FaultInjectingOperator",
    "truncate_latest_checkpoint",
    "lsmr",
    "lsmr_jit",
    "solve_sequence_lsmr",
    "solve_sequence_lsmr_jit",
    "GaussNewtonOperator",
    "GGNOperator",
    "KernelSystemOperator",
    "DenseMatrixOperator",
    "LinearOperator",
    "adjoint_matvec",
    "apply_to_basis",
    "from_callable",
    "from_matrix",
    "materialize",
    "JacobiPreconditioner",
    "NystromPreconditioner",
    "WoodburyKernelPreconditioner",
    "jacobi",
    "kernel_nystrom_preconditioner",
    "nystrom_preconditioner",
    "randomized_nystrom",
    "MAX_RECOVERY_RUNGS",
    "RecycleState",
    "SequenceResult",
    "harmonic_ritz",
    "harmonic_ritz_flat",
    "random_orthonormal_basis",
    "DEFAULT_WAW_JITTER",
    "CGResult",
    "RecycleData",
    "SolveInfo",
    "SolveStatus",
    "cg",
    "cholesky_solve",
    "defcg",
    "deflated_initial_guess",
    "HarmonicRitz",
    "MGeometryHarmonic",
    "RecycleStrategy",
    "WindowedRecombine",
]
