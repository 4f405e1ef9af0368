"""Public-API snapshot: the config surface must not silently fork again.

ISSUE 3 exists because five entry points each grew overlapping kwargs
with drifting defaults.  This test pins (a) ``repro.core.__all__`` and
(b) the exact ``SolveSpec`` field set + defaults, so any future PR that
adds a parallel config path (or quietly changes a shared default) fails
here and has to update the snapshot EXPLICITLY — with a reviewable diff.
"""

import dataclasses

import repro.core as core
from repro.core import HarmonicRitz, SolveSpec
from repro.core.solvers import DEFAULT_WAW_JITTER

# Alphabetical snapshot of the public surface.  Additions are fine (update
# deliberately); removals/renames are API breaks.
EXPECTED_CORE_ALL = sorted(
    [
        # front doors (core/api.py)
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
        # fault injection (ISSUE 6: chaos instrumentation)
        "FaultInjectingOperator",
        "truncate_latest_checkpoint",
        # operators
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
        # least-squares engine (ISSUE 9: the method axis)
        "lsmr",
        "lsmr_jit",
        "solve_sequence_lsmr",
        "solve_sequence_lsmr_jit",
        # preconditioners
        "JacobiPreconditioner",
        "NystromPreconditioner",
        "WoodburyKernelPreconditioner",
        "jacobi",
        "kernel_nystrom_preconditioner",
        "nystrom_preconditioner",
        "randomized_nystrom",
        # recycling
        "MAX_RECOVERY_RUNGS",
        "RecycleState",
        "SequenceResult",
        "harmonic_ritz",
        "harmonic_ritz_flat",
        "random_orthonormal_basis",
        # solvers
        "DEFAULT_WAW_JITTER",
        "CGResult",
        "RecycleData",
        "SolveInfo",
        "SolveStatus",
        "cg",
        "cholesky_solve",
        "defcg",
        "deflated_initial_guess",
        # recycle strategies (ISSUE 5: the extraction/refresh axis)
        "HarmonicRitz",
        "MGeometryHarmonic",
        "RecycleStrategy",
        "WindowedRecombine",
    ]
)

# The ONE solver-configuration schema.  Field name -> default.
EXPECTED_SOLVESPEC_FIELDS = {
    "method": "defcg",
    "k": 8,
    "ell": 12,
    "tol": 1e-5,
    "atol": 0.0,
    "maxiter": 1000,
    "select": "largest",
    "waw_jitter": DEFAULT_WAW_JITTER,
    "refresh_aw": "exact",
    "precond": "none",
    "precond_rank": 16,
    "precond_sigma": 1.0,
    "strategy": HarmonicRitz(),
    # ISSUE 6: the fault-tolerance knobs
    "recovery_rungs": 3,
    "recovery_shift": 1e-6,
    "stagnation_window": 0,
    # ISSUE 9: regularization shift λ for the least-squares methods
    "lsq_shift": 0.0,
}

# Failure-handling diagnostics returned by every front door.
EXPECTED_SOLVEREPORT_FIELDS = ("status", "rung", "guard_firings", "matvecs")


def test_solvereport_field_schema():
    from repro.core import SolveReport

    assert SolveReport._fields == EXPECTED_SOLVEREPORT_FIELDS


def test_core_all_snapshot():
    assert sorted(core.__all__) == EXPECTED_CORE_ALL


def test_core_all_resolves():
    for name in core.__all__:
        assert getattr(core, name) is not None, name


def test_solvespec_field_schema():
    fields = {f.name: f.default for f in dataclasses.fields(SolveSpec)}
    assert fields == EXPECTED_SOLVESPEC_FIELDS


def test_solvespec_frozen_and_hashable():
    spec = SolveSpec()
    assert hash(spec) == hash(SolveSpec())
    try:
        spec.k = 5  # type: ignore[misc]
    except dataclasses.FrozenInstanceError:
        pass
    else:  # pragma: no cover
        raise AssertionError("SolveSpec must be frozen")


def test_waw_jitter_never_forks():
    """The unified default is exactly 1e-12 everywhere it surfaces."""
    import inspect

    from repro.core import defcg
    from repro.core import recycle as recycle_mod

    assert DEFAULT_WAW_JITTER == 1e-12
    assert SolveSpec().waw_jitter == DEFAULT_WAW_JITTER
    assert (
        inspect.signature(defcg).parameters["waw_jitter"].default
        == DEFAULT_WAW_JITTER
    )
    assert (
        inspect.signature(recycle_mod.solve_sequence)
        .parameters["waw_jitter"]
        .default
        == DEFAULT_WAW_JITTER
    )
