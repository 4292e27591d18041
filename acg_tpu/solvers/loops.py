"""CG iteration bodies, parameterized over matvec and reduction.

One algorithm definition serves both the single-chip solver (plain
``jnp.vdot``) and the distributed solver (``psum``-reduced dots inside
``shard_map``): the distributed-memory structure of the reference collapses
to *which reduction function is passed in* — the loop is otherwise the same
compiled on-device ``while_loop`` (the monolithic-kernel analog,
reference acg/cg-kernels-cuda.cu:627-970).

``matvec`` is the full operator application (single-chip: one ELL SpMV;
distributed: local SpMV + halo exchange + interface SpMV, see
acg_tpu/solvers/cg_dist.py).  ``dot2`` fuses two reductions into one
reduction point — the pipelined variant's single 2-double allreduce
(reference acg/cgcuda.c:1694-1701).

MULTI-RHS (batched) mode: both loops accept ``b``/``x0`` of shape
``(B, n)`` — B independent systems against ONE operator, the request-
batching formulation that amortizes the matrix stream (the dominant HBM
traffic) across B right-hand sides (cf. the data-locality argument of
Kronbichler et al., arXiv 2205.08909).  All per-iteration scalars
(alpha, beta, rnrm2², the pipelined gamma/delta) become ``(B,)``
per-system vectors, ``dot`` must reduce over the LAST axis (a ``(B,)``
result), and the loop carries a per-system ACTIVE mask: a system that
converges (or breaks down) freezes — its x/r/p carries stop updating,
its residual_history stops advancing (NaN fill past its own exit), and
its per-system iteration count is pinned — while the while_loop runs
until every system is finished or maxits.  The 1-D path compiles to the
exact same program as before (batching is gated on static ``b.ndim``),
so B=1 via a 1-D vector is bit-for-bit today's solver.

LAYER SCOPES: every op of a loop carries one ``acg.*`` name scope
(acg_tpu/obs/trace.py): the operator applications ``acg.spmv``, the dots
and vector updates ``acg.blas1``, the loop predicate, scalar recurrences
and history writes ``acg.check``.  The scopes wrap the functions passed
in, so a caller's finer scopes inside them (the distributed halo stages,
the psum's ``acg.allreduce``) stay innermost.  Scopes are op metadata
only: the compiled program is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from acg_tpu.config import replace_period
from acg_tpu.robust.faults import (SITE_CARRY, SITE_HALO, SITE_SPMV,
                                   inject_reduction, inject_vector)

_OK, _CONVERGED, _BREAKDOWN, _FAULT = 0, 1, 2, 3
# s-step only: the Gram factorization went indefinite / non-finite (an
# ill-conditioned basis, or a non-SPD operator — the coefficient-space
# recurrence cannot tell them apart); the WRAPPER falls back to classic
# CG from the current iterate and says so in SolveResult.kernel_note
# (never silently wrong — ISSUE 7 acceptance)
_GRAM_BAD = 4


def _scoped(name: str, fn):
    """``fn`` with every call under ``jax.named_scope(name)``."""
    if fn is None:
        return None

    def call(*args):
        with jax.named_scope(name):
            return fn(*args)
    return call


def _history_init(rr0, maxits: int):
    """Fixed-size on-device convergence-history buffer: ``(maxits+1,)``
    residual-norm² samples, NaN-filled past the iterations actually run
    (the host trims to ``k+1``).  Slot k holds |r_k|² — slot 0 is the
    initial residual.  A dynamic-index write per iteration keeps the
    whole trajectory inside the ONE fused while_loop program: no fusion
    break, no host round-trip (the reference gets its per-iteration
    residual printout for free from its host-driven loop, acg/cg.c
    verbose mode; on TPU the loop never returns to the host, so the
    trajectory must ride the carry).  Batched ``rr0`` of shape (B,)
    yields a (B, maxits+1) buffer — one trajectory per system."""
    if rr0.ndim:
        return jnp.full((rr0.shape[0], maxits + 1), jnp.nan,
                        dtype=rr0.dtype).at[:, 0].set(rr0)
    return jnp.full((maxits + 1,), jnp.nan,
                    dtype=rr0.dtype).at[0].set(rr0)


def _scalar_of(rr):
    """The monitor hook consumes ONE scalar per emission; a batched solve
    streams its worst (maximum) per-system residual."""
    return jnp.max(rr) if rr.ndim else rr


def _maybe_monitor(monitor, monitor_every: int, k, rr):
    """Throttled live-progress tier: invoke ``monitor(k, rr)`` (a traced
    callable that internally performs a ``jax.debug.callback``) every
    ``monitor_every``-th iteration.  The lax.cond gate keeps quiet
    iterations free of host traffic; emission is asynchronous, so lines
    may trail the device by a few iterations."""
    if monitor is None or monitor_every <= 0:
        return
    jax.lax.cond(k % monitor_every == 0,
                 lambda args: monitor(*args),
                 lambda args: None, (k, rr))


def cg_while(matvec, dot, b, x0, stop2, diffstop, maxits: int,
             track_diff: bool, check_every: int = 1, coupled_step=None,
             segment: int = 0, carry_in=None, want_carry: bool = False,
             monitor=None, monitor_every: int = 0,
             fault=None, guard: bool = False, precond=None):
    """Classic CG loop (ref acg/cg.c:534-637 / acg/cgcuda.c:845-1020).

    Returns (x, k, rnrm2sqr, dxnrm2sqr, flag, rnrm2sqr0, hist) where
    ``hist`` is the ``(maxits+1,)`` residual-norm² history buffer
    (see :func:`_history_init`; NaN past iteration k).  ``stop2`` is the
    (atol², rtol²) pair; the threshold max(atol², rtol²·|r0|²) is formed on
    device.  ``dot`` must return a replicated scalar (psum'd if sharded).
    ``check_every`` tests convergence only every k-th iteration (a static
    int, so =1 compiles to the unconditional test; breakdown detection
    stays per-iteration) — the device-side analog of the reference's
    buffered residual checks (SURVEY §7 hard parts).

    The loop is the BETA-FIRST rotation of the textbook recurrence: the
    direction update p = r + βp opens the iteration (β carried from the
    previous step, β₀ = 0 with p₀ = 0 so the first direction is r₀) and is
    immediately followed by t = Ap and p'Ap.  The arithmetic sequence is
    identical to the update-last form; the rotation exists so those three
    ops sit adjacent, where ``coupled_step(r, p, beta) -> (p, t, p'Ap)``
    can compute them as ONE fused pass (the Pallas fused-SpMV+dot kernel,
    acg_tpu/ops/pallas_kernels.py — the TPU counterpart of the reference
    fusing its SpMV with the following cublasDdot on one stream,
    acg/cgcuda.c:858-894).  ``coupled_step=None`` derives the default from
    ``matvec``/``dot``.

    SEGMENTATION (SolverOptions.segment_iters): with ``segment > 0`` the
    while_loop additionally stops after ``segment`` iterations past the
    entry count; the caller re-invokes with ``carry_in`` (the
    ``want_carry=True`` extra return) until k reaches maxits or a flag
    fires.  The resumed loop is the SAME body on the SAME carry —
    numerically identical to the single-program solve.

    BATCHED mode (``b`` of shape (B, n); see module docstring): returns
    per-system k/rnrm2sqr/flag vectors of shape (B,) and a (B, maxits+1)
    history; converged systems freeze under the active mask while the
    loop runs to the last straggler.  The carry gains a per-system
    iteration-count element (the global k keeps driving segment limits),
    and ``dot`` must return per-system (B,) reductions.

    RESILIENCE (acg_tpu/robust/): ``fault`` is a
    :class:`~acg_tpu.robust.faults.DeviceFaultPlan` — a pytree of
    scalars selecting one deterministic corruption (site × iteration ×
    mode) applied inside the body via data-only ``where`` selection, so
    the program is identical across fault configurations.  ``guard``
    (static) enables the non-finiteness detector: at the existing
    ``check_every`` points the two ALREADY-REDUCED scalars of the
    iteration (|r|² and p'Ap — both replicated, so the test adds ZERO
    collectives) are tested finite, and a failure raises the ``_FAULT``
    flag, distinct from ``_BREAKDOWN`` (NaN poisons the comparisons the
    breakdown witness relies on, so without the guard a non-finite
    solve spins silently to maxits).  Both default off and then trace
    the exact pre-existing program.

    PRECONDITIONED (``precond``, a function z = M r for a symmetric
    positive-definite M; one right-hand side): the carry gains z = M r
    and r'z, the direction update becomes p = z + βp, α = r'z / p'Ap and
    β = r'z' / r'z.  Convergence, the history and the returned norms
    stay on |r|², so the residual keeps its meaning.  r'z <= 0 with
    |r|² > 0 proves M is not SPD and raises the breakdown flag, as the
    p'Ap witness does for A.  ``precond=None`` traces the unpreconditioned
    program exactly.
    """
    batched = b.ndim == 2
    if precond is not None and batched:
        raise ValueError("a preconditioned loop takes one right-hand side")
    precond = _scoped("acg.precond", precond)
    # broadcast a (B,) per-system scalar against (B, n) system vectors;
    # identity in the 1-D path, so that trace is unchanged
    bc = (lambda s: s[:, None]) if batched else (lambda s: s)
    matvec = _scoped("acg.spmv", matvec)
    dot = _scoped("acg.blas1", dot)
    if coupled_step is None:
        def coupled_step(r, p, beta):
            with jax.named_scope("acg.blas1"):
                p = r + bc(beta) * p
            t = matvec(p)
            return p, t, dot(p, t)
    coupled_step = _scoped("acg.spmv", coupled_step)

    if carry_in is None:
        r = b - matvec(x0)
        rr0 = dot(r, r)
    else:
        rr0 = carry_in[-1]
    atol2, rtol2 = stop2
    thresh2 = jnp.maximum(atol2, rtol2 * rr0)
    # an exactly-zero residual is convergence under ANY enabled criterion
    # (b = 0, or x0 already exact: thresh2 = rtol^2 * 0 = 0 and the strict
    # rr < thresh2 can never hold) — but with every criterion disabled
    # (the fixed-iteration timing protocol) the loop must still run to
    # maxits, so the rescue is gated on a criterion being enabled
    any_crit = (atol2 > 0.0) | (rtol2 > 0.0) | (diffstop > 0.0)

    def _met(rr):
        return (rr < thresh2) | (any_crit & (rr == 0.0))

    if carry_in is None:
        init_flag = jnp.where(_met(rr0), _CONVERGED, _OK).astype(jnp.int32)
        init = (x0, r, jnp.zeros_like(r), rr0, jnp.zeros_like(rr0),
                jnp.full_like(rr0, jnp.inf),
                jnp.asarray(0, jnp.int32), init_flag,
                _history_init(rr0, maxits))
        if precond is not None:
            z0 = precond(r)
            rz0 = dot(r, z0)
            init_flag = jnp.where(
                (init_flag == _OK) & (rz0 <= 0.0), _BREAKDOWN,
                init_flag).astype(jnp.int32)
            init = init[:7] + (init_flag,) + init[8:] + (z0, rz0)
        if batched:
            # per-system iteration counts (the global k cannot serve: a
            # system frozen at iteration 3 of a 40-iteration batch solve
            # must report 3)
            init = init + (jnp.zeros_like(init_flag),)
    else:
        init = carry_in[:-1]
    limit = (maxits if segment == 0
             else jnp.minimum(maxits, init[6] + segment))

    def cond(c):
        k, flag = c[6], c[7]
        alive = jnp.any(flag == _OK) if batched else (flag == _OK)
        return (k < limit) & alive

    def body(c):
        x, r, p, rr, beta, dxx, k, flag, hist, *rest = c
        ksys = rest if batched else []
        if precond is not None:
            z, rz = rest
        active = (flag == _OK) if batched else None
        # deterministic fault injection (no-ops tracing nothing when
        # fault is None): the residual carry and the halo-feeding
        # direction vector are corrupted at iteration entry, the SpMV
        # output after the operator application, the reduction result
        # after the dot — see acg_tpu/robust/faults.py for the site map
        r = inject_vector(fault, SITE_CARRY, k, r)
        p = inject_vector(fault, SITE_HALO, k, p)
        p_new, t, ptap = coupled_step(r if precond is None else z, p, beta)
        t = inject_vector(fault, SITE_SPMV, k, t)
        if batched:
            # frozen systems keep their direction (beta keeps recurring
            # on a frozen rr, so an unmasked p would drift — harmless to
            # x/r under alpha = 0, but kept finite and fixed on principle)
            with jax.named_scope("acg.blas1"):
                p_new = jnp.where(bc(active), p_new, p)
        p = p_new
        # Indefiniteness witness: for SPD A, p'Ap > 0 whenever p != 0, and
        # p != 0 whenever r != 0 (p·r = rr > 0), so p'Ap < 0 — or == 0
        # with rr > 0 — proves A is not SPD.  The remaining case,
        # p'Ap == 0 with rr == 0, is exact convergence (the f32 residual
        # of a fully-converged fixed-iteration timing solve underflows to
        # exactly zero): freeze the iterates (alpha = 0) and keep looping
        # to maxits instead of dying with a spurious "indefinite matrix".
        indefinite = (ptap < 0.0) | ((ptap == 0.0) & (rr > 0.0))
        safe = ptap > 0.0
        alpha = jnp.where(safe, (rr if precond is None else rz)
                          / jnp.where(safe, ptap, 1.0), 0.0)
        if batched:
            alpha = jnp.where(active, alpha, 0.0)   # freeze x and r
        with jax.named_scope("acg.blas1"):
            x = x + bc(alpha) * p
        if track_diff:
            dxx_new = alpha * alpha * dot(p, p)
            dxx = jnp.where(active, dxx_new, dxx) if batched else dxx_new
        with jax.named_scope("acg.blas1"):
            r = r - bc(alpha) * t
        rr_new = inject_reduction(fault, k, dot(r, r))
        if precond is not None:
            z = precond(r)
            rz_new = dot(r, z)
            indefinite = indefinite | ((rz_new <= 0.0) & (rr_new > 0.0))
        if batched:
            rr_new = jnp.where(active, rr_new, rr)
            # frozen systems' history stops advancing: their slots past
            # exit keep the NaN fill the host trims on
            hist = hist.at[:, k + 1].set(jnp.where(active, rr_new,
                                                   jnp.nan))
        else:
            hist = hist.at[k + 1].set(rr_new)
        _maybe_monitor(monitor, monitor_every, k + 1, _scalar_of(rr_new))
        converged = _met(rr_new) | (
            (diffstop > 0.0) & (dxx < diffstop) if track_diff else False)
        if check_every > 1:
            converged = converged & ((k + 1) % check_every == 0)
        flag_new = jnp.where(indefinite, _BREAKDOWN,
                             jnp.where(converged, _CONVERGED,
                                       _OK)).astype(jnp.int32)
        if guard:
            # finiteness guard on the two scalars this iteration ALREADY
            # reduced (|r|² and p'Ap): no new collectives, evaluated at
            # the existing check_every points.  A NaN/Inf anywhere in the
            # recurrence reaches one of them within an iteration or two
            # (a non-finite t freezes alpha via the safe-guard, but keeps
            # p — and therefore p'Ap — non-finite forever), so the guard
            # cannot miss a persistent non-finite state.
            nonfin = ~(jnp.isfinite(rr_new) & jnp.isfinite(ptap))
            at_check = ((k + 1) % check_every == 0) if check_every > 1 \
                else True
            flag_new = jnp.where(at_check & nonfin, _FAULT,
                                 flag_new).astype(jnp.int32)
        if batched:
            flag = jnp.where(active, flag_new, flag)
            ksys = [jnp.where(active, k + 1, ksys[0])]
        else:
            flag = flag_new
        if precond is not None:
            beta_next = rz_new / jnp.where(rz == 0.0, 1.0, rz)
            return (x, r, p, rr_new, beta_next, dxx, k + 1, flag, hist,
                    z, rz_new)
        beta_next = rr_new / jnp.where(rr == 0.0, 1.0, rr)
        return (x, r, p, rr_new, beta_next, dxx, k + 1, flag,
                hist) + tuple(ksys)

    out = jax.lax.while_loop(_scoped("acg.check", cond),
                             _scoped("acg.check", body), init)
    x, r, p, rr, beta, dxx, k, flag, hist = out[:9]
    # tolerance met at exit IS convergence, whatever the flag: rr is a true
    # dot(r,r), and with check_every>1 the loop may pass the unobserved
    # convergence point and then either hit maxits (flag _OK) or trip a
    # breakdown guard on the stagnated machine-precision residual
    flag = jnp.where(_met(rr), _CONVERGED, flag).astype(jnp.int32)
    kret = out[9] if batched else k
    if want_carry:
        return x, kret, rr, dxx, flag, rr0, hist, out + (rr0,)
    return x, kret, rr, dxx, flag, rr0, hist


def _leja_order(v):
    """Leja ordering of a shift set (last axis; batched rows order
    independently): first the largest-magnitude point, then greedily the
    point maximizing the product of distances to the points already
    chosen.  The standard Newton-basis stabilization (Philippe/Reichel):
    monomial-ordered shifts lose the conditioning the shifts exist to
    buy.  The length is static (s <= 16), so the greedy loop unrolls."""
    s = v.shape[-1]
    if s == 1:
        return v

    def take(i):
        return jnp.take_along_axis(v, i[..., None], axis=-1)[..., 0]

    idx = jnp.argmax(jnp.abs(v), axis=-1)
    out = [take(idx)]
    picked = jnp.arange(s) == idx[..., None]
    logprod = jnp.zeros(v.shape, v.dtype)
    for _ in range(s - 1):
        logprod = logprod + jnp.log(
            jnp.abs(v - out[-1][..., None]) + jnp.asarray(1e-30, v.dtype))
        idx = jnp.argmax(jnp.where(picked, -jnp.inf, logprod), axis=-1)
        out.append(take(idx))
        picked = picked | (jnp.arange(s) == idx[..., None])
    return jnp.stack(out, axis=-1)


def _newton_basis_matrix(shifts, s: int):
    """Change-of-basis matrix B with A·V = V·B on the first s (resp.
    s-1) columns of the P (resp. R) Newton basis block: the basis
    recurrence V[j+1] = (A - θ_j)V[j] gives A·V[j] = V[j+1] + θ_j·V[j],
    so B is the P/R-blocked sub-diagonal of ones plus θ on the diagonal.
    The spill columns (degree-s P, degree-(s-1) R) are zero — the inner
    recurrences never apply A to them (coefficient support grows by one
    degree per step, Carson's CA-CG closure).  ``shifts`` is ([B,] s);
    batched shifts produce a ([B,] m, m) stack."""
    m = 2 * s + 1
    sub = np.zeros((m, m), dtype=np.float64)
    for j in range(s):
        sub[j + 1, j] = 1.0
    for j in range(s - 1):
        sub[s + 2 + j, s + 1 + j] = 1.0
    sub = jnp.asarray(sub, dtype=shifts.dtype)
    zero1 = jnp.zeros(shifts.shape[:-1] + (1,), shifts.dtype)
    theta = jnp.concatenate(
        [shifts, zero1,
         jax.lax.slice_in_dim(shifts, 0, s - 1, axis=-1), zero1],
        axis=-1)
    return sub + theta[..., :, None] * jnp.eye(m, dtype=shifts.dtype)


def cg_sstep_while(block_fn, b, x0, p0, rr0, shifts0, stop2, s: int,
                   maxits: int, monitor=None, monitor_every: int = 0):
    """s-step (communication-reduced) CG loop (arXiv:2501.03743): ONE
    Gram reduction per s iterations.

    Per outer while-loop step, ``block_fn(x, p, shifts)`` returns
    ``(V, G)``: the (2s+1)-vector Krylov basis over the owned rows —
    rows 0..s the Newton-shifted P-block [p, (A-θ_0)p, ...], rows
    s+1..2s the R-block seeded with the REPLACED residual r = b - A·x
    (residual replacement every outer block is built in, not optional:
    the basis builder recomputes r from its definition, so the exit test
    below always sees a true residual at block boundaries) — and its
    Gram matrix G = V·Vᵀ, reduced through ONE fused tall-skinny matmul
    (ops/blas1.py ``gram``; the distributed builder psums G as its ONE
    collective, and hoists the halo exchange of the (x, p) seeds to once
    per block through the deep ghost zones of acg_tpu/parallel/deep.py).

    The s inner updates then run as pure local recurrences on the Gram
    COEFFICIENTS: every CG inner product <u, v> with u = u'ᵀV, v = v'ᵀV
    is u'ᵀGv' (a (2s+1)-vector contraction), and A·V is the static
    change-of-basis matrix of :func:`_newton_basis_matrix` — zero
    collectives, zero vector-length work inside the block.

    Exit discipline: convergence is DECIDED only at block boundaries on
    the replaced (true) residual — G[s+1, s+1] = |b - Ax|² exactly.  An
    inner-step estimate below tolerance merely pauses that system's
    updates; the next block either certifies it (flag _CONVERGED) or,
    when the estimate lied (drift), resumes iterating on the freshly
    replaced state — the s-step analog of the pipelined loop's exit
    certification (the check_every-overshoot bug class the fuzzer found
    there is exactly what this prevents).  Callers certify the final
    state once more after the loop (the maxits door).

    Newton shifts ride the carry: each complete block's inner (α, β)
    sequence forms the Lanczos tridiagonal whose eigenvalues are the
    Ritz estimates of A; the next block's basis uses them Leja-ordered
    (on-the-fly refinement — the monomial basis is numerically dead past
    s≈4).  ``shifts0`` seeds block 0 (callers pass Chebyshev points of a
    Gershgorin interval, or zeros).

    Any indefinite/non-finite Gram quantity flags ``_GRAM_BAD`` with the
    block's bad updates ROLLED BACK (x keeps its last good state); the
    wrapper falls back to classic CG.  Returns
    (x, kiter, rr, flag, hist, shifts); batched ``b`` of shape (B, n)
    gives per-system kiter/rr/flag vectors and a (B, maxits+1) history
    written at each system's OWN iteration cursor (systems pause and
    resume, so rows stay contiguous per system)."""
    batched = b.ndim == 2
    # the builder scopes its own operator applications acg.spmv; its
    # shifted updates and Gram product default to acg.blas1
    block_fn = _scoped("acg.blas1", block_fn)
    bc = (lambda v: v[..., None])       # coefficient-axis broadcast:
    # identity-shaped for scalars (() -> (1,)), per-system for (B,)
    vdt = b.dtype
    m = 2 * s + 1
    atol2, rtol2 = stop2
    thresh2 = jnp.maximum(atol2, rtol2 * rr0)
    any_crit = (atol2 > 0.0) | (rtol2 > 0.0)
    one = jnp.asarray(1.0, vdt)

    def _met(rr):
        return (rr < thresh2) | (any_crit & (rr == 0.0))

    e_p = jnp.zeros((m,), vdt).at[0].set(1.0)
    e_r = jnp.zeros((m,), vdt).at[s + 1].set(1.0)
    if batched:
        B = b.shape[0]
        e_p = jnp.tile(e_p, (B, 1))
        e_r = jnp.tile(e_r, (B, 1))

    def hist_put(hist, pos, mask, val):
        """Write ``val`` at each system's own cursor ``pos`` where
        ``mask``; elsewhere keep the current content (the frozen-system
        discipline of the other loops, but at PER-SYSTEM positions —
        systems pause and resume, so the global k cannot serve)."""
        if batched:
            # one dynamic_update_slice per system: the per-row cursor
            # form hist.at[rows, pos] lowers to a scatter (contract C5)
            for i in range(B):
                at = (jnp.asarray(i, pos.dtype), pos[i])
                cur = jax.lax.dynamic_slice(hist, at, (1, 1))
                hist = jax.lax.dynamic_update_slice(
                    hist, jnp.where(mask[i], val[i], cur), at)
            return hist
        return hist.at[pos].set(jnp.where(mask, val, hist[pos]))

    ksys0 = (jnp.zeros((B,), jnp.int32) if batched
             else jnp.asarray(0, jnp.int32))
    flag0 = jnp.zeros(jnp.shape(rr0), jnp.int32)
    init = (x0, p0, rr0, shifts0, ksys0, flag0,
            _history_init(rr0, maxits))

    def cond(c):
        kiter, flag = c[4], c[5]
        live = (flag == _OK) & (kiter < maxits)
        return jnp.any(live) if batched else live

    def body(c):
        x, p, rr, shifts, kiter, flag, hist = c
        V, G = block_fn(x, p, shifts)
        # the R-seed is the REPLACED residual: its Gram diagonal is the
        # true |b - Ax|² — the certified quantity every exit stands on
        rr_true = G[..., s + 1, s + 1]
        gfin = jnp.all(jnp.isfinite(G), axis=(-2, -1))
        # divergence guard: an ill-conditioned basis can commit garbage
        # for MANY blocks while every coefficient-space quantity stays
        # finite and positive (the Newton basis overflows gradually, the
        # recurred rr_j is wildly inaccurate long before the Gram goes
        # non-finite) — but the block boundary sees the TRUE |b - Ax|²,
        # so a residual far above its starting value is caught here,
        # within ~a block of going wrong, while the iterate is still
        # recoverable.  CG's residual 2-norm may oscillate above |r0|
        # transiently (it minimizes the A-norm of the error), so the
        # bound carries 1e4 headroom (100x on the norm); beyond it the
        # recurrence has lost the plot and classic CG takes over.
        difn = gfin & ~_met(rr_true) \
            & (rr_true > jnp.asarray(1e4, vdt) * rr0)
        active0 = flag == _OK
        flag = jnp.where(active0 & (~gfin | difn), _GRAM_BAD,
                         jnp.where(active0 & _met(rr_true), _CONVERGED,
                                   flag)).astype(jnp.int32)
        # overwrite each live system's last sample with the true value
        # (drift-corrected trajectory, like the pipelined certification
        # points)
        hist = hist_put(hist, kiter, active0 & gfin, rr_true)
        _maybe_monitor(monitor, monitor_every,
                       jnp.max(kiter) if batched else kiter,
                       _scalar_of(jnp.where(active0, rr_true, rr)))
        active = flag == _OK
        Bmat = _newton_basis_matrix(shifts, s)

        kiter0 = kiter
        pc, rc = e_p, e_r
        xc = jnp.zeros_like(pc)
        rr_j = rr_true
        conv_est = jnp.zeros(jnp.shape(rr0), bool)
        bad = jnp.zeros(jnp.shape(rr0), bool)
        allok = active
        # the coefficient-space roundoff floor: quadratic forms c'Gc
        # carry absolute error ~ m·eps·max|G|·|c|², so a tiny-NEGATIVE
        # value within that bound is benign cancellation near the
        # attainable floor (the system pauses and the NEXT block's
        # replaced residual re-scales the basis), NOT an indefinite
        # Gram — only beyond-floor negativity triggers the classic-CG
        # fallback (the CA-CG near-convergence hazard, Carson §5)
        gmax = jnp.max(jnp.abs(G), axis=(-2, -1))
        eps = jnp.asarray(4.0 * m * jnp.finfo(vdt).eps, vdt)
        alphas, betas = [], []
        for _ in range(s):
            w = jnp.einsum("...ij,...j->...i", Bmat, pc)
            Gw = jnp.einsum("...ij,...j->...i", G, w)
            denom = jnp.sum(pc * Gw, axis=-1)
            step = active & ~bad & ~conv_est & (kiter < maxits)
            zerofrozen = step & (rr_j == 0.0)
            attempt = step & (rr_j > 0.0)
            floor_p = eps * gmax * jnp.sum(pc * pc, axis=-1)
            benign_d = attempt & (denom <= 0.0) & jnp.isfinite(denom) \
                & (jnp.abs(denom) <= floor_p)
            conv_est = conv_est | benign_d      # pause at the floor
            indef = attempt & ~benign_d \
                & ((denom <= 0.0) | ~jnp.isfinite(denom))
            bad = bad | indef
            do = attempt & ~indef & ~benign_d
            alpha = jnp.where(do, rr_j / jnp.where(do, denom, one), 0.0)
            xc2 = xc + bc(alpha) * pc
            rc2 = rc - bc(alpha) * w
            Grc = jnp.einsum("...ij,...j->...i", G, rc2)
            rr_n = jnp.sum(rc2 * Grc, axis=-1)
            floor_r = eps * gmax * jnp.sum(rc2 * rc2, axis=-1)
            rr_n = jnp.where((rr_n < 0.0) & (jnp.abs(rr_n) <= floor_r),
                             0.0, rr_n)
            ok2 = jnp.isfinite(rr_n) & (rr_n >= 0.0)
            bad = bad | (do & ~ok2)
            commit = do & ok2
            xc = jnp.where(bc(commit), xc2, xc)
            rc = jnp.where(bc(commit), rc2, rc)
            counted = commit | zerofrozen
            kiter = kiter + counted.astype(jnp.int32)
            hist = hist_put(hist, kiter, counted,
                            jnp.where(commit, rr_n, rr_j))
            conv_est = conv_est | (commit & _met(rr_n))
            beta = jnp.where(commit,
                             rr_n / jnp.where(rr_j == 0.0, one, rr_j),
                             0.0)
            pc = jnp.where(bc(commit), rc2 + bc(beta) * pc, pc)
            alphas.append(alpha)
            betas.append(beta)
            allok = allok & commit
            rr_j = jnp.where(commit, rr_n, rr_j)

        # bad blocks roll back by construction (only committed steps
        # touched xc) — and the contraction itself is GATED on a step
        # having committed: a non-finite basis (overflowed shifts, NaN
        # Gram) would otherwise poison x through 0·inf = NaN even with
        # all-zero coefficients
        changed = kiter > kiter0
        # a live block that committed NOTHING can never progress (the
        # next block would rebuild the identical basis): classify as
        # _GRAM_BAD so the wrapper's classic-CG fallback takes over —
        # the progress guarantee that makes the benign floor-pause
        # above safe from livelock
        stalled = active & ~changed & (kiter < maxits)
        flag = jnp.where(active & (bad | stalled), _GRAM_BAD,
                         flag).astype(jnp.int32)
        with jax.named_scope("acg.blas1"):
            if batched:
                x = jnp.where(changed[:, None],
                              x + jnp.einsum("bm,mbn->bn", xc, V), x)
                p = jnp.where(changed[:, None],
                              jnp.einsum("bm,mbn->bn", pc, V), p)
            else:
                x = jnp.where(changed,
                              x + jnp.einsum("m,mn->n", xc, V), x)
                p = jnp.where(changed, jnp.einsum("m,mn->n", pc, V), p)

        # on-the-fly Ritz refinement: a COMPLETE block's (α, β) sequence
        # is a Lanczos tridiagonal; its eigenvalues (Ritz estimates of
        # A's spectrum) become the next block's Newton shifts, Leja-
        # ordered.  Incomplete/degenerate blocks keep the old shifts.
        a = jnp.stack(alphas, axis=-1)
        bt = jnp.stack(betas, axis=-1)
        a_safe = jnp.where(a > 0.0, a, one)
        diag = 1.0 / a_safe

        def head(t):    # t[..., : s-1], gather-free (lint rule E1)
            return jax.lax.slice_in_dim(t, 0, s - 1, axis=-1)

        diag = diag.at[..., 1:].add(head(bt) / head(a_safe))
        off = jnp.sqrt(jnp.maximum(head(bt), 0.0)) / head(a_safe)
        # off_j couples rows (j, j+1): pad to length s so row j of the
        # k=+1 wing carries off_j, row j+1 of the k=-1 wing carries off_j
        zpad = [(0, 0)] * (off.ndim - 1)
        off_hi = jnp.pad(off, zpad + [(0, 1)])
        off_lo = jnp.pad(off, zpad + [(1, 0)])
        T = (diag[..., :, None] * jnp.eye(s, dtype=vdt)
             + off_hi[..., :, None] * jnp.eye(s, k=1, dtype=vdt)
             + off_lo[..., :, None] * jnp.eye(s, k=-1, dtype=vdt))
        valid = allok
        T = jnp.where(bc(valid)[..., None] if batched else valid,
                      T, jnp.eye(s, dtype=vdt))
        ritz = jnp.linalg.eigvalsh(T)
        new_shifts = _leja_order(ritz).astype(vdt)
        good = valid & jnp.all(jnp.isfinite(new_shifts), axis=-1) \
            & jnp.all(new_shifts > 0.0, axis=-1)
        shifts = jnp.where(bc(good) if batched else good,
                           new_shifts, shifts)
        return (x, p, rr_j, shifts, kiter, flag, hist)

    out = jax.lax.while_loop(_scoped("acg.check", cond),
                             _scoped("acg.check", body), init)
    x, p, rr, shifts, kiter, flag, hist = out
    return x, kiter, rr, flag, hist, shifts


def cg_pipelined_while(matvec, dot2, b, x0, stop2, maxits: int,
                       check_every: int = 1, replace_every: int = 0,
                       certify: bool = True, iter_step=None,
                       monitor=None, monitor_every: int = 0,
                       fault=None, guard: bool = False,
                       segment: int = 0, carry_in=None,
                       want_carry: bool = False):
    """Pipelined CG loop; ONE fused reduction point per iteration.

    ``dot2(a1, b1, a2, b2)`` returns (a1·b1, a2·b2) through a single
    reduction (distributed: one psum of a length-2 vector — the reference's
    one 2-double allreduce, acg/cgcuda.c:1697).  The (γ, δ) pair is carried
    so the convergence test in the loop predicate adds no extra reduction
    (ref cgcuda.c:1759-1772 tests before the fused update).
    Returns (x, k, gamma, flag, gamma0, hist); ``hist`` is the
    ``(maxits+1,)`` residual-norm² history (:func:`_history_init`) —
    NOTE it records the RECURRED gamma per iteration (what the exit test
    sees), except at certification points, where the freshly replaced
    true residual is recorded instead: exactly the trajectory needed to
    observe recurrence drift and tune ``replace_every``
    (arXiv:1801.04728's deep-pipeline drift analysis).

    ``replace_every=R`` performs residual replacement every R iterations
    (Cools/Vanroose-style): the recurred r, w, s, z drift from their true
    values by accumulated rounding, stalling the attainable accuracy of
    pipelined CG; periodically recomputing r = b - Ax, w = Ar, s = Ap,
    z = As restores it at the cost of 4 extra operator applications and
    one extra reduction per replacement step.  ``None`` resolves by the
    vector dtype (:func:`replace_period`): fp32 recurrences drift past
    rtol 1e-5 within ~50 iterations at 128³ and diverge without it.  The
    reference ships no such correction — its pipelined solver simply
    stalls at the drift floor.

    Every replaced state (periodic or certification) also RE-DERIVES the
    next step's scalars from it: beta = -(r, s)/(p, s) keeps the next
    direction A-conjugate to p, and the denominator (p', Ap') becomes
    delta + beta (r, s).  Carrying the recurred gamma_prev/alpha_prev
    across a replacement instead mixes a true gamma into a drifted
    beta = gamma/gamma_prev, and in fp32 the resulting denominators
    threw x off by 100x per replacement (PR 21: 160³ took 1,734
    iterations at R=10 and failed at R=20; with the re-derivation
    419-430 for R = 10..100, classic CG 413).  The re-derivation needs p
    to still be a direction of the true problem: where the true gamma is
    more than 4x off the recurred one (a drift gap, typically a failed
    exit certification past the attainable floor) or the pair cannot
    give a positive step (r.s >= 0 or p.s <= 0), the next step restarts
    from r instead (beta = 0).

    Exit CERTIFICATION: the recurred gamma is a drifting estimate, and
    past the attainable floor it decouples downward while the TRUE
    residual grows — with ``check_every`` > 1 the loop can overshoot real
    convergence and the recurred value then certifies a wrong answer
    (found by differential fuzz: f32, check_every=7, true residual 7e-3
    against a claimed 2e-6).  So any iteration whose recurred gamma
    passes the exit test REPLACES r, w, s, z from their definitions and
    re-reduces: the exit decision is made on the true residual, at the
    cost of one replacement step per exit candidate (usually exactly
    one per solve).  A failed certification leaves the state freshly
    replaced and the loop simply continues.  The reference's pipelined
    solver exits on the raw recurred value (acg/cgcuda.c:1759-1772) and
    carries exactly this false-certificate risk.

    ``iter_step(z, r, p, w, s, x, alpha, beta)``, when given, performs
    the WHOLE iteration body — q = Aw, the 6-vector update, and the
    (gamma, delta) reduction — returning (z', p', s', x', r', w', gamma,
    delta): the single-kernel pipelined iteration
    (acg_tpu/ops/pallas_kernels.py cg_pipelined_iter_pallas), where q
    never exists in HBM and the dot operands are never re-read.
    Replacement steps recompute the recurrences through ``matvec`` after
    the kernel's step, exactly as the open-coded body does.

    ``certify=False`` (static) removes the in-body certification branch
    entirely.  Callers pass it exactly when NO stopping criterion is
    enabled (fixed-iteration solves, the benchmark protocol): no exit can
    be claimed, so there is nothing to certify — and the lax.cond the
    certifier otherwise adds carries 6 full vectors through an XLA
    conditional every iteration, whose restricted buffer aliasing showed
    up as ~4 extra vector streams/iter in the round-4 pipelined numbers
    (3,588 it/s at 128³ vs the formulation's ~5.0k byte-model ceiling;
    see PERF.md round 5 for the authoritative decomposition).

    Breakdown handling: the recurred denominator delta - beta*gamma/alpha
    estimates p'Ap through quantities that drift; once the solve reaches
    its attainable-accuracy floor the estimate routinely goes non-positive
    and beta explodes on noise ratios, so a non-positive denominator
    triggers an automatic RESTART — this step freezes (alpha=beta=0) and
    the next step re-derives the directions from the current r, w
    (beta=0, denom=delta), exactly like iteration 0.  Indefiniteness is
    deliberately NOT diagnosed here: the drifting estimate cannot
    distinguish an indefinite operator from floor noise, and the
    reference's pipelined solver has no breakdown check at all
    (acg/cgcuda.c:1676-1788 checks only CUDA/comm error codes; it would
    produce NaNs where this loop restarts) — use classic CG or the host
    oracle to diagnose indefiniteness.

    SEGMENTATION (SolverOptions.segment_iters, wired in PR 7 — the
    classic loop got it in PR 5): with ``segment > 0`` the while_loop
    additionally stops after ``segment`` iterations past the entry
    count; ``carry_in`` (the ``want_carry=True`` extra return, whose
    last element is gamma0) re-enters the SAME body on the exact loop
    state — numerically identical to the monolithic solve.  The
    post-loop certification below runs per segment but only shapes that
    segment's returned values, never the carry.

    RESILIENCE: ``fault``/``guard`` as in :func:`cg_while`.  The guard
    here rides the loop PREDICATE — γ and δ are both in the carry, and
    the cond already reads them every iteration, so testing them finite
    adds no reduction and no collective; a non-finite pair exits the
    loop and the post-loop flag becomes ``_FAULT``.  ``fault`` requires
    ``iter_step=None`` (the single-kernel iteration exposes no
    injection sites; callers gate the mega-kernel off for injection
    solves).
    """
    batched = b.ndim == 2
    replace_every = replace_period(replace_every, b.dtype)
    matvec = _scoped("acg.spmv", matvec)
    dot2 = _scoped("acg.blas1", dot2)
    # the single-kernel iteration fuses the SpMV with the vector updates
    # and the dots: one op, counted as the SpMV
    iter_step = _scoped("acg.spmv", iter_step)
    if carry_in is None:
        r = b - matvec(x0)
        w = matvec(r)
        gamma0, delta0 = dot2(r, r, w, r)
    else:
        # SEGMENTATION (SolverOptions.segment_iters, the pipelined twin
        # of cg_while's carry-resume): the caller re-enters the SAME
        # body on the exact carry; gamma0 rides in the carry (second to
        # last, before the device-computed continue bit) so the
        # threshold is rebuilt identically
        gamma0 = carry_in[-2]
        delta0 = None
    # broadcast (B,) per-system scalars against (B, n) vectors; identity
    # on the 1-D path (whose trace is unchanged — see module docstring)
    bc = (lambda v: v[:, None]) if batched else (lambda v: v)
    if batched and iter_step is not None:
        raise ValueError("iter_step (the single-kernel pipelined "
                         "iteration) is not batched; callers gate it off "
                         "for multi-RHS solves")
    atol2, rtol2 = stop2
    thresh2 = jnp.maximum(atol2, rtol2 * gamma0)
    # exactly-zero residual = converged when a criterion is enabled (see
    # cg_while; thresh2 is 0 and strict < can never fire when gamma0 = 0)
    any_crit = (atol2 > 0.0) | (rtol2 > 0.0)
    zero = jnp.zeros_like(b)
    one = jnp.asarray(1.0, b.dtype)
    zero_s = jnp.zeros_like(gamma0)

    def _met(g):
        return (g < thresh2) | (any_crit & (g == 0.0))

    def _exit_test(g, kk):
        """The exit predicate, shared verbatim by cond and the in-body
        certification so every loop exit passes through a certified
        (freshly replaced) gamma."""
        done = _met(g)
        if check_every > 1:
            done = done & (kk % check_every == 0)
        return done

    def _replace_step(args):
        """Recompute the recurred vectors from their definitions and
        re-reduce: (r, w, s, z, gamma, delta, r.s, p.s) — the last two
        re-derive the next step's scalars (see docstring)."""
        x, _, _, p, _, _ = args
        with jax.named_scope("acg.blas1"):
            r = b - matvec(x)
        w = matvec(r)
        s = matvec(p)
        z = matvec(s)
        g, d = dot2(r, r, w, r)
        rs, ps = dot2(r, s, p, s)
        return r, w, s, z, g, d, rs, ps

    def _keep(args, g, d):
        """The not-replaced branch of every replacement cond."""
        return args[1], args[2], args[4], args[5], g, d, zero_s, zero_s

    if carry_in is not None:
        init = carry_in[:-2]
    limit = (maxits if segment == 0
             else jnp.minimum(maxits,
                              (carry_in[10] if carry_in is not None
                               else 0) + segment))

    def cond(c):
        gamma, k = c[6], c[10]
        if batched:
            # run until every system is finished (c[14] is the per-system
            # done mask) or maxits
            return (k < limit) & ~jnp.all(c[14])
        alive = jnp.asarray(True)
        if guard:
            # finiteness guard on the carried (γ, δ) pair — the cond
            # already reads the carry, so this is free of reductions and
            # collectives; a non-finite pair stops the loop and the
            # post-loop flag becomes _FAULT
            alive = jnp.isfinite(gamma) & jnp.isfinite(c[7])
        return (k < limit) & ~_exit_test(gamma, k) & alive

    if iter_step is not None and fault is not None:
        raise ValueError("fault injection requires iter_step=None (the "
                         "single-kernel pipelined iteration exposes no "
                         "injection sites)")

    def body(c):
        (x, r, w, p, s, z, gamma, delta, gamma_prev, alpha_prev, k, fresh,
         certified, hist) = c[:14]
        # deterministic fault injection (identity tracing nothing when
        # fault is None): the residual carry, and w — the vector whose
        # border values feed the halo exchange of q = Aw
        r = inject_vector(fault, SITE_CARRY, k, r)
        w = inject_vector(fault, SITE_HALO, k, w)
        if batched:
            done, ksys = c[14], c[15]
            active = ~done
            olds = (x, r, w, p, s, z)
        beta = jnp.where(fresh, 0.0, gamma / jnp.where(gamma_prev == 0.0,
                                                       one, gamma_prev))
        denom = jnp.where(fresh, delta,
                          delta - beta * gamma / jnp.where(
                              alpha_prev == 0.0, one, alpha_prev))
        # unusable denominator -> restart (see docstring): freeze this
        # step and re-derive the directions from r, w on the next one
        bad = (denom <= 0.0) | (~fresh & (gamma_prev == 0.0))
        alpha = jnp.where(bad, 0.0, gamma / jnp.where(bad, one, denom))
        beta = jnp.where(bad, 0.0, beta)
        if iter_step is not None:
            z, p, s, x, r, w, gamma_new, delta_new = iter_step(
                z, r, p, w, s, x, alpha, beta)
        else:
            q = matvec(w)   # overlaps the reduction in the sharded case
            q = inject_vector(fault, SITE_SPMV, k, q)
            # fused 6-vector update (ref acg/cg-kernels-cuda.cu:187-269);
            # XLA fuses these into one pass over the 7 vector streams
            with jax.named_scope("acg.blas1"):
                z = q + bc(beta) * z
                p = r + bc(beta) * p
                s = w + bc(beta) * s
                x = x + bc(alpha) * p
                r = r - bc(alpha) * s
                w = w - bc(alpha) * z
            gamma_new, delta_new = dot2(r, r, w, r)
            gamma_new = inject_reduction(fault, k, gamma_new)
        g_rec = gamma_new            # recurred, before any replacement
        if replace_every > 0:
            just_replaced = (k + 1) % replace_every == 0
            r, w, s, z, gamma_new, delta_new, rs, ps = jax.lax.cond(
                just_replaced, _replace_step,
                lambda a: _keep(a, gamma_new, delta_new),
                (x, r, w, p, s, z))
        else:
            just_replaced = jnp.asarray(False)
            rs = ps = zero_s

        # exit certification (see docstring): a recurred gamma that would
        # exit the loop is re-derived from the true residual before the
        # exit decision stands — paid only on candidate iterations
        if certify:
            cand = _exit_test(gamma_new, k + 1)
            if batched:
                # per-system certification: replacement state is computed
                # once for the whole batch when ANY active system is an
                # exit candidate, then blended in per system
                cand = cand & active
                need = cand & ~just_replaced

                def _certify_sel(args):
                    rc, wc, sc, zc, gc, dc, rsc, psc = _replace_step(args)
                    m = bc(need)
                    with jax.named_scope("acg.blas1"):
                        vs = (jnp.where(m, rc, args[1]),
                              jnp.where(m, wc, args[2]),
                              jnp.where(m, sc, args[4]),
                              jnp.where(m, zc, args[5]))
                    return vs + (jnp.where(need, gc, gamma_new),
                                 jnp.where(need, dc, delta_new),
                                 jnp.where(need, rsc, rs),
                                 jnp.where(need, psc, ps))

                r, w, s, z, gamma_new, delta_new, rs, ps = jax.lax.cond(
                    jnp.any(need), _certify_sel,
                    lambda a: _keep(a, gamma_new, delta_new)[:6] + (rs, ps),
                    (x, r, w, p, s, z))
            else:
                # a just-replaced gamma_new IS the true residual — don't
                # redo the identical replacement in the certifier
                need = cand & ~just_replaced
                r, w, s, z, gamma_new, delta_new, rs2, ps2 = jax.lax.cond(
                    need, _replace_step,
                    lambda a: _keep(a, gamma_new, delta_new),
                    (x, r, w, p, s, z))
                rs = jnp.where(need, rs2, rs)
                ps = jnp.where(need, ps2, ps)
        else:
            cand = need = jnp.asarray(False)
        # the scalars the NEXT step derives beta and its denominator from:
        # re-derived from a replaced state, else the recurred ones
        replaced = just_replaced | need
        usable = ((rs < 0.0) & (ps > 0.0) & (gamma_new <= 4.0 * g_rec)
                  & (g_rec <= 4.0 * gamma_new))
        beta_r = -rs / jnp.where(usable, ps, one)
        gamma_prev_next = jnp.where(
            replaced & usable,
            gamma_new / jnp.where(usable, beta_r, one), gamma)
        alpha_prev_next = jnp.where(
            replaced & usable, -gamma_new / jnp.where(usable, rs, one),
            alpha)
        bad = bad | (replaced & ~usable)
        if batched:
            # freeze finished systems: carries, per-system scalars, and
            # the history row all stop advancing
            m = bc(active)
            with jax.named_scope("acg.blas1"):
                x, r, w, p, s, z = (jnp.where(m, v, o) for v, o in
                                    zip((x, r, w, p, s, z), olds))
            gamma_new = jnp.where(active, gamma_new, gamma)
            delta_new = jnp.where(active, delta_new, delta)
            gamma_prev = jnp.where(active, gamma_prev_next, gamma_prev)
            alpha_prev = jnp.where(active, alpha_prev_next, alpha_prev)
            fresh = jnp.where(active, bad, fresh)
            certified = jnp.where(active, cand | just_replaced, certified)
            hist = hist.at[:, k + 1].set(jnp.where(active, gamma_new,
                                                   jnp.nan))
            _maybe_monitor(monitor, monitor_every, k + 1,
                           _scalar_of(gamma_new))
            # the exit decision per system, on the (certified) gamma —
            # exactly the predicate the 1-D cond applies
            done = done | (active & _exit_test(gamma_new, k + 1))
            if guard:
                # the per-system face of the 1-D cond's finiteness guard
                done = done | (active & ~(jnp.isfinite(gamma_new)
                                          & jnp.isfinite(delta_new)))
            ksys = jnp.where(active, k + 1, ksys)
            return (x, r, w, p, s, z, gamma_new, delta_new, gamma_prev,
                    alpha_prev, k + 1, fresh, certified, hist, done, ksys)
        hist = hist.at[k + 1].set(gamma_new)
        _maybe_monitor(monitor, monitor_every, k + 1, gamma_new)
        return (x, r, w, p, s, z, gamma_new, delta_new, gamma_prev_next,
                alpha_prev_next, k + 1, bad, cand | just_replaced, hist)

    if carry_in is None:
        true0 = jnp.full(jnp.shape(gamma0), True)
        init = (x0, r, w, zero, zero, zero, gamma0, delta0, gamma0,
                jnp.zeros_like(gamma0), jnp.asarray(0, jnp.int32),
                true0, true0,           # gamma0 is true: certified
                _history_init(gamma0, maxits))
        if batched:
            # systems converged at x0 are done before the first iteration
            # — the same k=0 exit the 1-D cond takes
            init = init + (_exit_test(gamma0, 0),
                           jnp.zeros(gamma0.shape, jnp.int32))
    out = jax.lax.while_loop(_scoped("acg.check", cond),
                             _scoped("acg.check", body), init)
    (x, r, w, p, s, z, gamma, delta, gamma_prev, alpha, k, fresh,
     certified, hist) = out[:14]
    # the maxits door can be reached off the check_every schedule with an
    # uncertified recurred gamma below threshold — certify that one too
    # (a single extra reduction, outside the loop)
    def _true_gamma(xv):
        rt = b - matvec(xv)
        wt = matvec(rt)
        g, _ = dot2(rt, rt, wt, rt)
        return g

    if certify and batched:
        need = _met(gamma) & ~certified
        gamma = jax.lax.cond(
            jnp.any(need),
            lambda xv: jnp.where(need, _true_gamma(xv), gamma),
            lambda xv: gamma, x)
        # each system's last live sample equals its certified exit value
        # (systems that exited through the in-body certifier already hold
        # it — this rewrite is the identity for them)
        ksys = out[15]
        hist = hist.at[jnp.arange(gamma.shape[0]), ksys].set(gamma)
        flag = jnp.where(_met(gamma), _CONVERGED, _OK).astype(jnp.int32)
    elif certify:
        gamma = jax.lax.cond(_met(gamma) & ~certified, _true_gamma,
                             lambda _: gamma, x)
        # keep the trajectory's last sample equal to the certified exit
        # value (slot k may hold the uncertified recurred gamma)
        hist = hist.at[k].set(gamma)
        flag = jnp.where(_met(gamma), _CONVERGED, _OK).astype(jnp.int32)
    else:
        # no criterion enabled: nothing can be claimed converged
        flag = jnp.full(jnp.shape(gamma), _OK, jnp.int32)
    if guard:
        # a non-finite (γ, δ) pair is what stopped the loop (see cond):
        # report it as the _FAULT flag, distinct from breakdown — the
        # NaN poisons every comparison above, so no other branch can
        # have claimed the exit
        flag = jnp.where(~(jnp.isfinite(gamma) & jnp.isfinite(delta)),
                         _FAULT, flag).astype(jnp.int32)
    kret = out[15] if batched else k
    if want_carry:
        # the carry is the RAW loop state (`out`): the post-loop
        # certification above only shapes this segment's RETURNED
        # gamma/flag/hist, so a resumed segment re-enters exactly the
        # state the monolithic program would carry.  `more` is the
        # UNSEGMENTED loop predicate evaluated on that state — the host
        # driver continues on this device-computed bit, so the segment
        # boundary can never diverge from the monolithic cond (no host
        # re-derivation of the f32 threshold arithmetic)
        if batched:
            more = (out[10] < maxits) & ~jnp.all(out[14])
        else:
            alive = jnp.asarray(True)
            if guard:
                alive = jnp.isfinite(out[6]) & jnp.isfinite(out[7])
            more = ((out[10] < maxits)
                    & ~_exit_test(out[6], out[10]) & alive)
        return x, kret, gamma, flag, gamma0, hist, out + (gamma0, more)
    return x, kret, gamma, flag, gamma0, hist


def cg_pipelined_deep_while(matvec, dots, dot, b, x0, stop2, depth: int,
                            shifts, maxits: int, check_every: int = 1,
                            replace_every: int = 0, certify: bool = True,
                            k_start=None, rr0_in=None, flags_in=None,
                            hist_in=None, ksys_in=None, fill=None,
                            cert_matvec=None, monitor=None,
                            monitor_every: int = 0,
                            guard: bool = False):
    """Depth-*l* pipelined CG: *l* global reductions in flight.

    The p(l)-CG formulation (Cornelis/Cools/Vanroose arXiv:1801.04728,
    with the global-reduction pipelining refinement of arXiv:1905.06850):
    the iteration runs on the SHIFTED-NEWTON auxiliary basis
    z_j = p_l(A) v_{j-l} (p_{k+1}(t) = (t - sigma_k) p_k(t), Leja-ordered
    Chebyshev shifts — the same stabilization the s-step basis uses,
    :func:`_leja_order` / ``cg._cheb_leja_nodes``), whose three-term
    recurrence needs the Lanczos coefficients (gamma, delta) only at lag
    *l*.  Each body therefore issues ONE SpMV and ONE fused dot-block
    reduction — the (2l+1) inner products (z_new, z_m) — and consumes
    the block issued *l* bodies ago: exactly *l* reductions are in
    flight, overlapping *l* iterations of allreduce latency where the
    one-deep pipelined loop overlaps one.

    Per body, with c = t+1 the column finalized and t the x-update
    performed (t = k - k_start, the updates this dispatch):

      1. pop the l-old dot block; forward-substitute column c of the
         banded basis-change factor G (z_i = sum_j g_{j,i} v_j; the
         band is 2l+1 wide — p_l(A) v_{i-l} spreads both UP and DOWN
         the Krylov basis, A being tridiagonal in it) from the Gram
         identity (z_c, z_m) = sum_k g_{k,m} g_{k,c};
      2. read off (gamma_t, delta_t) from T G = G B (B the shift-
         companion of the z recurrence — sigma-based columns while
         t < l, recurrence-based after) and recover the Lanczos vector
         v_c = (z_c - sum g_{k,c} v_k)/g_{c,c};
      3. advance x by the D-Lanczos (LDL) update — lam = delta_{t-1}/
         d_{t-1}, d_t = gamma_t - delta_{t-1} lam, zeta_t =
         -lam zeta_{t-1}, q_t = v_t - lam q_{t-1}, x += (zeta_t/d_t) q_t
         — whose residual norm |r_t| = delta_t |zeta_t| / d_t is a FREE
         scalar exit estimate (no extra reduction);
      4. one SpMV: z_{new} = (A z_top - gamma_{t} z_top -
         delta_{t-1} z_prev)/delta_t (the steady recurrence; the fill
         recurrence z_{j+1} = (A - sigma_j) z_j ran in ``fill`` before
         the loop), then ONE reduced dot block against the (2l+1)-window,
         pushed into the FIFO.

    ``dots(U, v)`` returns the ([B,] 2l+1) block of inner products of
    each row of U with v through ONE reduction (distributed: one psum of
    (2l+1)·B values — the "1 psum per iteration" the deep contract
    declares).  ``dot(u, v)`` is the plain single reduced dot.
    ``fill(z0)`` returns the (l+1, [B,] n) stack [z_0..z_l] of the fill
    phase; None derives the default l-matvec chain from ``matvec`` —
    the distributed caller passes the deep-ghost matrix-power chain
    (ONE depth-l exchange feeding the SpMV skin,
    acg_tpu/parallel/deep.py) instead.

    DISPATCH PROTOCOL (restart = residual replacement): this function
    runs ONE pipeline segment — fill outside the loop, steady bodies
    inside — and every re-entry recomputes r = b - A x from its
    definition, so re-dispatching IS residual replacement.  The loop
    stops early (flag _OK, ``more`` true) when ``replace_every`` updates
    have run, or when the scalar estimate claims convergence; the
    POST-LOOP certifier then derives the TRUE residual (one matvec + one
    reduction, outside the audited body) and only a true value below
    threshold flags _CONVERGED.  ``drift`` reports an estimate that
    claimed convergence the true residual refuted — the caller counts
    consecutive drift/breakdown dispatches and falls back to classic CG
    (the s-step _GRAM_BAD discipline).  ``k_start``/``rr0_in``/
    ``flags_in``/``hist_in``/``ksys_in`` are OPERANDS (pass
    0/0.0/_OK-zeros/anything on the first dispatch), so every dispatch
    — first or resumed — runs the SAME compiled program.

    Breakdown witnesses: a non-positive LDL pivot d_t or a non-positive
    Cholesky diagonal g_{c,c}² (the Gram factorization went indefinite —
    basis overflow or drift) freezes that system with flag _BREAKDOWN
    and NO commit of the bad update; ``guard`` additionally tests the
    already-reduced per-body scalars finite (flag _FAULT, zero new
    collectives).  Fault injection is not supported here (callers gate
    deep solves off injection plans, like s-step).

    Returns (x, kret, rr, flag, rr0, hist, k, more, drift): ``rr`` is
    the certified true |r|² (``certify``) or the last estimate; ``k``
    the global update count to pass back as the next ``k_start``;
    ``more`` the device-computed continue bit.  Batched ``b`` (B, n)
    makes kret/rr/flag/drift per-system (B,) with the usual frozen-
    system discipline."""
    batched = b.ndim == 2
    # the deep pipeline's segments are its replacement schedule; unset
    # means one segment (its drift ladder restarts, see docstring)
    replace_every = replace_every or 0
    # window width: the basis-change band is 2l+1 (p_l(A) v_{i-l}
    # spreads l rows DOWN the Krylov basis as well as up)
    l, w = depth, 2 * depth + 1
    if l < 2:
        raise ValueError("cg_pipelined_deep_while requires depth >= 2 "
                         "(depth 1 is cg_pipelined_while)")
    vdt = b.dtype
    bc = (lambda v: v[:, None]) if batched else (lambda v: v)
    one = jnp.asarray(1.0, vdt)
    atol2, rtol2 = stop2
    matvec = _scoped("acg.spmv", matvec)
    dots = _scoped("acg.blas1", dots)
    dot = _scoped("acg.blas1", dot)

    first = k_start == 0 if k_start is not None else jnp.asarray(True)
    k0 = (jnp.asarray(0, jnp.int32) if k_start is None
          else k_start.astype(jnp.int32))

    # cert_matvec: the operator the entry residual and the exit
    # certificate stand on — the distributed caller passes the
    # UNCOMPRESSED (f32-wire) exchange here when the hot loop runs a
    # compressed halo wire, so certificates stay honest against the
    # real operator; both sites are outside the audited body
    cmv = matvec if cert_matvec is None else cert_matvec

    # entry state: r from its definition (re-entry IS residual
    # replacement), eta the Lanczos scale of THIS segment's basis
    r = b - cmv(x0)
    eta2 = dot(r, r)
    rr0 = (eta2 if rr0_in is None
           else jnp.where(rr0_in > 0.0, rr0_in, eta2))
    thresh2 = jnp.maximum(atol2, rtol2 * rr0)
    any_crit = (atol2 > 0.0) | (rtol2 > 0.0)

    def _met(g):
        return (g < thresh2) | (any_crit & (g == 0.0))

    def _exit_test(g, kk):
        done = _met(g)
        if check_every > 1:
            done = done & (kk % check_every == 0)
        return done

    eta = jnp.sqrt(eta2)
    inv_eta = jnp.where(eta2 > 0.0, one / jnp.where(eta2 > 0.0, eta, one),
                        0.0)
    z0 = bc(inv_eta) * r

    if fill is None:
        def fill(zz):
            zs = [zz]
            for j in range(l):
                zc = zs[-1]
                zs.append(matvec(zc) - bc(shifts[..., j]) * zc)
            return jnp.stack(zs, axis=0)

    Zs = fill(z0)                        # (l+1, [B,] n): z_0..z_l
    # prefill dot blocks for the first l pops: D_j holds (z_{j+1}, z_m),
    # m = j+1-2l..j+1; rows with m < 0 dot against an all-zero row and
    # come out exactly 0 (the band mask, for free)
    Zbig = jnp.concatenate([jnp.zeros((2 * l,) + z0.shape, vdt), Zs],
                           axis=0)       # Zbig[r] = z_{r-2l}
    dbuf0 = jnp.stack(
        [dots(jax.lax.slice_in_dim(Zbig, j + 1, j + 1 + w, axis=0),
              Zs[j + 1]) for j in range(l)], axis=0)   # (l, [B,] w)
    Z0 = jax.lax.slice_in_dim(Zbig, l, l + w, axis=0)  # z_{-l}..z_l

    sshape = jnp.shape(eta2)             # ([B],) per-system scalars
    V0 = jnp.zeros((w,) + z0.shape, vdt).at[w - 1].set(Zs[0])  # v_0 = z_0
    G0 = jnp.zeros(sshape + (w, w), vdt)
    G0 = G0.at[..., w - 1, w - 1].set(1.0)           # g_{0,0} = 1
    gbuf0 = jnp.zeros((l,) + sshape, vdt)            # gamma_{t-l..t-1}
    dlbuf0 = jnp.zeros((l,) + sshape, vdt)           # delta_{t-l..t-1}

    flag0 = (jnp.zeros(sshape, jnp.int32) if flags_in is None
             else flags_in.astype(jnp.int32))
    # the entry residual is TRUE by construction: meeting the threshold
    # here is certified convergence, no loop body needed
    flag0 = jnp.where((flag0 == _OK) & _met(eta2), _CONVERGED,
                      flag0).astype(jnp.int32)
    est0 = jnp.zeros(sshape, bool)
    hist = _history_init(rr0, maxits)
    if hist_in is not None:
        hist = jnp.where(first, hist, hist_in)
    if batched:
        rows = jnp.arange(b.shape[0])
        # per-system update counts are CUMULATIVE across dispatches
        # (ksys_in is the previous dispatch's kret; systems frozen in an
        # earlier dispatch keep their counts)
        ksys0 = (jnp.zeros(sshape, jnp.int32) if ksys_in is None
                 else ksys_in.astype(jnp.int32))
    shifts_b = shifts.astype(vdt)

    def _sigma(i):
        # sigma_i without a dynamic gather (the hot loop stays
        # gather-free on the DIA tier, contracts rule E1): masked sum
        # over the static-length shift axis
        return jnp.sum(jnp.where(jnp.arange(l) == i, shifts_b, 0.0),
                       axis=-1)

    init = (x0, jnp.zeros_like(b), Z0, V0, G0, dbuf0, gbuf0, dlbuf0,
            jnp.zeros(sshape, vdt), jnp.zeros(sshape, vdt), eta2,
            k0, flag0, est0, hist)
    if batched:
        init = init + (ksys0,)

    def cond(c):
        k, flag, est = c[11], c[12], c[13]
        live = (flag == _OK) & ~est
        live = jnp.any(live) if batched else live
        going = (k < maxits) & live
        if replace_every > 0:
            going = going & (k - k0 < replace_every)
        return going

    def body(c):
        (x, q, Z, V, G, dbuf, gbuf, dlbuf, d_prev, zeta_prev, rr_est,
         k, flag, est, hist) = c[:15]
        active = (flag == _OK) & ~est
        t = k - k0                       # x-update index this dispatch

        # 1. pop the l-old block and finalize column c = t+1 of G
        D = dbuf[0]                      # ([B,] w)
        Gr = jnp.zeros_like(G).at[..., : w - 1, : w - 1].set(
            G[..., 1:, 1:])  # static slide [c-2l, c]  # acg: allow-gather
        col = []                         # g_{c-2l..c-1, c}, forward subst.
        for a in range(w - 1):
            acc = D[..., a]
            for kk in range(a):
                # kk, a are Python ints: static picks  # acg: allow-gather
                acc = acc - col[kk] * Gr[..., kk, a]
            gaa = Gr[..., a, a]
            ok = gaa != 0.0              # rows m < 0 carry zeros: g = 0
            col.append(jnp.where(ok, acc / jnp.where(ok, gaa, one), 0.0))
        gcc2 = D[..., w - 1]
        for kk in range(w - 1):
            gcc2 = gcc2 - col[kk] * col[kk]
        good_g = gcc2 > 0.0              # Cholesky diagonal stays SPD
        gcc = jnp.sqrt(jnp.maximum(gcc2, 0.0))
        Gr = Gr.at[..., : w - 1, w - 1].set(jnp.stack(col, axis=-1))
        Gr = Gr.at[..., w - 1, w - 1].set(gcc)

        # 2. Lanczos coefficients at index t from T G = G B: the B
        # column is sigma-based while t < l (fill-phase polynomial
        # degree still growing), recurrence-based after
        sel_fill = t < l
        base = jnp.where(sel_fill, _sigma(t), gbuf[0])      # gamma_{t-l}
        mult = jnp.where(sel_fill, one, dlbuf[0])           # delta_{t-l}
        d_tm1 = dlbuf[l - 1]                                # delta_{t-1}
        gii = Gr[..., w - 2, w - 2]                         # g_{t, t}
        gii_s = jnp.where(gii != 0.0, gii, one)
        gam_t = base + (col[w - 2] * mult
                        - d_tm1 * Gr[..., w - 3, w - 2]) / gii_s
        del_t = gcc * mult / gii_s
        # recover v_c (the basis vector the NEXT l bodies' updates ride)
        gcc_s = jnp.where(gcc != 0.0, gcc, one)
        with jax.named_scope("acg.blas1"):
            vsum = jnp.zeros_like(b)
            for a in range(w - 1):
                vsum = vsum + bc(col[a]) * V[a + 1]
            v_c = bc(one / gcc_s) * (Z[l + 1] - vsum)

        # 3. D-Lanczos x-update at index t (residual estimate for free)
        is0 = t == 0
        dp_s = jnp.where(d_prev != 0.0, d_prev, one)
        lam = jnp.where(is0, 0.0, d_tm1 / dp_s)
        dd = gam_t - d_tm1 * lam
        zeta = jnp.where(is0, eta, -lam * zeta_prev)
        dd_s = jnp.where(dd != 0.0, dd, one)
        rr_new = (del_t * zeta / dd_s) ** 2

        bad = (dd <= 0.0) | ~good_g
        commit = active & ~bad
        with jax.named_scope("acg.blas1"):
            q_new = V[w - 1] - bc(lam) * q
            x_new = x + bc(zeta / dd_s) * q_new
            x = jnp.where(bc(commit), x_new, x)
            q = jnp.where(bc(commit), q_new, q)
        d_prev = jnp.where(commit, dd, d_prev)
        zeta_prev = jnp.where(commit, zeta, zeta_prev)
        rr_est = jnp.where(commit, rr_new, rr_est)
        flag = jnp.where(active & bad, _BREAKDOWN, flag).astype(jnp.int32)
        if guard:
            # already-reduced per-body scalars only: no new collectives
            nonfin = ~(jnp.isfinite(rr_new) & jnp.isfinite(gcc2))
            at_check = ((k + 1) % check_every == 0) if check_every > 1 \
                else True
            flag = jnp.where(active & at_check & nonfin, _FAULT,
                             flag).astype(jnp.int32)
        est = est | (commit & _exit_test(rr_new, k + 1))
        stepped = jnp.any(commit) if batched else commit
        k_new = k + stepped.astype(jnp.int32)
        if batched:
            hist = hist.at[:, k + 1].set(jnp.where(commit, rr_new,
                                                   jnp.nan))
            ksys = jnp.where(commit, k + 1, c[15])
        else:
            hist = hist.at[k + 1].set(jnp.where(commit, rr_new,
                                                hist[k + 1]))
        _maybe_monitor(monitor, monitor_every, k + 1,
                       _scalar_of(jnp.where(commit, rr_new, rr_est)))

        # 4. ONE SpMV + ONE reduced dot block (the audited body cost);
        # the window recurrences are per-lane, so frozen systems' lanes
        # may keep evolving harmlessly (their scalars are masked above)
        z_top, z_prev = Z[w - 1], Z[w - 2]
        wv = matvec(z_top)
        c_s = jnp.where(del_t != 0.0, del_t, one)
        with jax.named_scope("acg.blas1"):
            z_new = bc(one / c_s) * (wv - bc(gam_t) * z_top
                                     - bc(d_tm1) * z_prev)
            Z = jnp.concatenate([Z[1:], z_new[None]], axis=0)
            V = jnp.concatenate([V[1:], v_c[None]], axis=0)
        D_new = dots(Z, z_new)           # the ONE psum of the body
        dbuf = jnp.concatenate([dbuf[1:], D_new[None]], axis=0)
        gbuf = jnp.concatenate([gbuf[1:], gam_t[None]], axis=0)
        dlbuf = jnp.concatenate([dlbuf[1:], del_t[None]], axis=0)
        ret = (x, q, Z, V, Gr, dbuf, gbuf, dlbuf, d_prev, zeta_prev,
               rr_est, k_new, flag, est, hist)
        if batched:
            ret = ret + (ksys,)
        return ret

    out = jax.lax.while_loop(_scoped("acg.check", cond),
                             _scoped("acg.check", body), init)
    (x, q, Z, V, G, dbuf, gbuf, dlbuf, d_prev, zeta_prev, rr_est,
     k, flag, est, hist) = out[:15]
    touched = flag == _OK                # systems this dispatch drove
    if certify:
        # TRUE-residual exit certification, once per dispatch and
        # OUTSIDE the audited body: only a fresh |b - Ax|² below the
        # threshold may flag _CONVERGED; an estimate it refutes is
        # reported as drift for the caller's fallback counter
        rt = b - cmv(x)
        rr_true = dot(rt, rt)
        met_t = _met(rr_true)
        flag = jnp.where(touched & met_t, _CONVERGED,
                         flag).astype(jnp.int32)
        drift = touched & est & ~met_t
        rr_ret = jnp.where(touched, rr_true, rr_est)
        if batched:
            ksys = out[15]
            cur = hist[rows, ksys]
            hist = hist.at[rows, ksys].set(
                jnp.where(touched, rr_true, cur))
        else:
            hist = hist.at[k].set(jnp.where(touched, rr_true, hist[k]))
    else:
        drift = jnp.zeros(jnp.shape(rr_est), bool)
        rr_ret = rr_est
    more_sys = (flag == _OK) & (k < maxits)
    more = jnp.any(more_sys) if batched else more_sys
    kret = out[15] if batched else k
    return x, kret, rr_ret, flag, rr0, hist, k, more, drift
