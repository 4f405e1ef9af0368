"""Executable trace audit: DESIGN.md §6's compile-time discipline as a gate.

Three properties of the solver front doors (``solve`` /
``solve_sequence`` / ``solve_batch``) are load-bearing for serving and
cannot be checked statically, so this module *runs* them (tiny problems,
n≈24, a few iterations) and turns every breach into a ``trace-audit``
:class:`~repro.analysis.engine.Violation`:

1. **No tracer leaks.** Every audit runs under
   ``jax.check_tracer_leaks()`` — a traced value escaping into Python
   state raises instead of silently capturing a stale tracer.

2. **Retrace budgets.** A spec-identical repeat call (same shapes,
   dtypes, static spec — new values) must hit the jit cache: ≤1 trace
   for ``solve``/``solve_sequence``/``solve_batch``, measured on fresh
   ``jax.jit`` wrappers via ``_cache_size()``.  The chunked
   (checkpointed) ``solve_sequence`` is a host loop over eager engine
   scans; its budget is ≤2 ``scan`` compilations per run shape (the
   full-chunk program + one trailing partial chunk, the PR 6 claim) and
   **zero** new XLA compilations on an identical re-run, measured by
   capturing ``jax.log_compiles()`` output.

3. **No forbidden host primitives.** The lowered jaxpr of each clean
   path must not contain ``io_callback`` / ``pure_callback`` /
   ``debug_callback`` — a host callback in the hot loop serializes the
   device stream every iteration.  (Intentional host hops — fault
   instrumentation, checkpointing — live OUTSIDE these clean paths.)

Budget: the whole audit is a handful of n=24 CPU solves — seconds, not
minutes — so CI runs it on every push (the ``lint`` job).
"""

from __future__ import annotations

import contextlib
import logging
import re
import tempfile
from typing import Iterator, List, Tuple

from repro.analysis.engine import Violation

FORBIDDEN_PRIMITIVES = ("io_callback", "pure_callback", "debug_callback")


def fresh_jit(fn, **jit_kwargs):
    """``jax.jit`` with a PRIVATE trace cache.

    jit's tracing cache is keyed on the underlying function object and
    shared across every wrapper of it — ``jax.jit(api.solve)._cache_size()``
    counts traces from *all* callers of ``solve`` in the process,
    including module-level ``solve_jit`` and other tests.  A fresh
    forwarding wrapper (``functools.wraps`` preserves the signature, so
    ``static_argnames`` still resolves) isolates the measurement.
    """
    import functools

    import jax

    @functools.wraps(fn)
    def isolated(*args, **kwargs):
        return fn(*args, **kwargs)

    return jax.jit(isolated, **jit_kwargs)

# One record per actual XLA compilation (jax._src.interpreters.pxla).
_COMPILE_RE = re.compile(r"^Compiling ([\w<>\[\]\.-]+) with global shapes")


def _violation(message: str, source: str = "") -> Violation:
    return Violation(
        rule="trace-audit", path="trace_audit", line=0, col=0,
        message=message, source=source,
    )


# ---------------------------------------------------------------------------
# compile-event capture
# ---------------------------------------------------------------------------


class _CompileCapture(logging.Handler):
    """Collects the names of XLA compilations logged by
    ``jax.log_compiles()``."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.names: List[str] = []

    def emit(self, record):
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self.names.append(m.group(1))


@contextlib.contextmanager
def count_compiles() -> Iterator[_CompileCapture]:
    """Context manager yielding a live list of XLA compile events."""
    import jax

    cap = _CompileCapture()
    logger = logging.getLogger("jax")
    old_level = logger.level
    logger.addHandler(cap)
    if not logger.isEnabledFor(logging.WARNING):
        logger.setLevel(logging.WARNING)
    try:
        with jax.log_compiles():
            yield cap
    finally:
        logger.removeHandler(cap)
        logger.setLevel(old_level)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(value):
    from jax.extend import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in ``jaxpr``, recursing into sub-jaxprs
    (scan/while/cond bodies, pjit calls, shard_map and Pallas kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def iter_primitives(jaxpr) -> Iterator[str]:
    """Every primitive name in ``jaxpr`` (see :func:`iter_eqns`)."""
    for eqn in iter_eqns(jaxpr):
        yield eqn.primitive.name


def reduced_precision_dots(closed_jaxpr) -> List[str]:
    """The ``dot_general``s that do not ask for ``Precision.HIGHEST``.

    On a TPU such a contraction of f32 operands runs as one bf16 pass.
    """
    import jax

    full = (jax.lax.Precision.HIGHEST,) * 2
    return [
        "dot_general("
        + ", ".join(v.aval.str_short() for v in eqn.invars)
        + f") precision={eqn.params['precision']}"
        for eqn in iter_eqns(closed_jaxpr.jaxpr)
        if eqn.primitive.name == "dot_general"
        and eqn.params["precision"] != full
    ]


def find_forbidden(closed_jaxpr) -> List[str]:
    hits = [
        p
        for p in iter_primitives(closed_jaxpr.jaxpr)
        if any(p.startswith(f) for f in FORBIDDEN_PRIMITIVES)
    ]
    return sorted(set(hits))


# ---------------------------------------------------------------------------
# tiny audit problems (pure jnp; deterministic)
# ---------------------------------------------------------------------------


def _audit_problem(n: int = 24, num: int = 5, seed: int = 0):
    """A short drifting SPD sequence — small enough that the full audit
    is a few seconds of CPU."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (n, n)) / jnp.sqrt(n)
    base = q @ q.T + jnp.eye(n)
    shifts = 0.05 * jnp.arange(num, dtype=base.dtype)
    mats = base[None] + shifts[:, None, None] * jnp.eye(n)[None]
    bs = jax.random.normal(jax.random.fold_in(key, 1), (num, n))
    return mats, bs


def _audit_spec():
    from repro.core import SolveSpec

    return SolveSpec(k=3, ell=4, tol=1e-6, maxiter=40)


# ---------------------------------------------------------------------------
# the three audits
# ---------------------------------------------------------------------------


def audit_forbidden_primitives() -> List[Violation]:
    """Lower each front door's clean path and scan the jaxpr."""
    import jax

    from repro.core import RecycleState, from_matrix
    from repro.core import api as api_mod

    spec = _audit_spec()
    mats, bs = _audit_problem()
    n = bs.shape[-1]
    state0 = RecycleState.zeros(spec.k, n, bs.dtype)
    out: List[Violation] = []

    def check(name, fn, *args):
        with jax.check_tracer_leaks():
            jaxpr = jax.make_jaxpr(fn)(*args)
        hits = find_forbidden(jaxpr)
        if hits:
            out.append(_violation(
                f"front door `{name}` lowers forbidden host "
                f"primitive(s) {hits}: a host callback in the hot loop "
                "serializes the device stream",
                source=name,
            ))

    check(
        "solve",
        lambda A, b, st: api_mod.solve(from_matrix(A), b, spec, st),
        mats[0], bs[0], state0,
    )
    check(
        "solve_sequence",
        lambda ms, vs, st: api_mod.solve_sequence(
            ms, vs, spec, st, make_operator=from_matrix
        ),
        mats, bs, state0,
    )
    import jax.numpy as jnp

    bstate = jax.tree_util.tree_map(
        lambda l: jnp.stack([l, l]), state0
    )
    check(
        "solve_batch",
        lambda ms, vs, st: api_mod.solve_batch(
            ms, vs, spec, st, make_operator=from_matrix
        ),
        mats[:2], bs[:2], bstate,
    )
    return out


def audit_retrace_budgets() -> List[Violation]:
    """Spec-identical repeats must not retrace (≤1 cached trace each)."""
    import jax

    from repro.core import RecycleState, from_matrix
    from repro.core import api as api_mod

    spec = _audit_spec()
    mats, bs = _audit_problem()
    n = bs.shape[-1]
    state0 = RecycleState.zeros(spec.k, n, bs.dtype)
    out: List[Violation] = []

    # NOTE: no `jax.check_tracer_leaks()` here — leak checking re-traces
    # every call (it disables the jit cache), which would make any
    # compile-count measurement meaningless.  Leak checking runs in
    # audit_forbidden_primitives, where only the lowering matters.
    def budget(name, fn, budget_traces, calls, **kwargs):
        for args in calls:
            fn(*args, **kwargs)
        traces = fn._cache_size()
        if traces > budget_traces:
            out.append(_violation(
                f"`{name}` traced {traces}× across spec-identical calls "
                f"(budget {budget_traces}): something in the call "
                "signature is not cache-stable",
                source=name,
            ))

    solve_f = fresh_jit(
        api_mod.solve,
        static_argnames=("spec", "record_residuals", "batch_axis"),
    )
    budget(
        "solve", solve_f, 1,
        [
            (from_matrix(mats[0]), bs[0], spec, state0),
            (from_matrix(mats[1]), bs[1], spec, state0),
        ],
    )

    seq_f = jax.jit(
        lambda ms, vs, st: api_mod.solve_sequence(
            ms, vs, spec, st, make_operator=from_matrix
        )
    )
    budget(
        "solve_sequence", seq_f, 1,
        [(mats, bs, state0), (mats + 0.01, bs + 1.0, state0)],
    )

    import jax.numpy as jnp

    bstate = jax.tree_util.tree_map(lambda l: jnp.stack([l, l]), state0)
    batch_f = fresh_jit(
        api_mod.solve_batch,
        static_argnames=(
            "spec", "make_operator", "make_preconditioner",
            "sequence", "carry_x",
        ),
    )
    budget(
        "solve_batch", batch_f, 1,
        [
            (mats[:2], bs[:2], spec, bstate),
            (mats[1:3], bs[1:3], spec, bstate),
        ],
        make_operator=from_matrix,
    )
    return out


def audit_chunked_sequence() -> List[Violation]:
    """The chunked (crash-resumable) ``solve_sequence`` budget:

    * ≤2 ``scan`` compilations on a cold run (full chunk + trailing
      partial chunk — the PR 6 claim), and
    * ZERO new XLA compilations on a spec/shape-identical re-run.
    """
    import jax

    from repro.checkpoint import CheckpointManager
    from repro.core import from_matrix
    from repro.core import api as api_mod

    spec = _audit_spec()
    mats, bs = _audit_problem(num=5)
    out: List[Violation] = []

    def run(directory):
        return api_mod.solve_sequence(
            mats, bs, spec, None,
            make_operator=from_matrix,
            checkpoint=CheckpointManager(directory),
            checkpoint_every=2,
        )

    # No leak-check context here either (it would defeat the caches this
    # audit exists to measure) — see audit_retrace_budgets.
    with tempfile.TemporaryDirectory() as d1:
        with count_compiles() as cold:
            run(d1)
    # The chunk engine is one module-level jit (`_solve_sequence_spec`);
    # count its compilations plus any bare eager scans that leak out.
    scans = [
        n for n in cold.names if n == "scan" or "solve_sequence" in n
    ]
    if len(scans) > 2:
        out.append(_violation(
            f"chunked solve_sequence compiled {len(scans)} scan "
            "programs on a cold run (budget 2: full chunk + "
            "trailing partial)",
            source="solve_sequence[chunked] cold",
        ))
    with tempfile.TemporaryDirectory() as d2:
        with count_compiles() as warm:
            run(d2)
    if warm.names:
        out.append(_violation(
            f"chunked solve_sequence re-run recompiled "
            f"{len(warm.names)} program(s) ({sorted(set(warm.names))}) "
            "despite identical spec/shapes: the host loop is "
            "breaking XLA's eager cache",
            source="solve_sequence[chunked] warm",
        ))
    return out


def run_trace_audit() -> Tuple[List[Violation], List[str]]:
    """Run all three audits; returns (violations, progress lines)."""
    lines = []
    out: List[Violation] = []
    for name, fn in (
        ("forbidden-primitives", audit_forbidden_primitives),
        ("retrace-budgets", audit_retrace_budgets),
        ("chunked-sequence", audit_chunked_sequence),
    ):
        vs = fn()
        lines.append(
            f"trace-audit/{name}: {'OK' if not vs else f'{len(vs)} violation(s)'}"
        )
        out.extend(vs)
    return out, lines
