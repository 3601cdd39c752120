"""Common model interface (L4).

Mirrors the reference's ``TimeSeriesModel`` trait (SURVEY.md Section 2.2:
``addTimeDependentEffects`` / ``removeTimeDependentEffects``) as a pair of
pure functions on parameter pytrees, plus the fit-result container shared by
every model family.

Conventions:
- Every model module exposes ``fit(y, ...) -> FitResult`` accepting ``[time]``
  or ``[batch, time]`` (auto-vmapped), with all structure (orders, seasonality)
  static so one compiled computation fits the whole batch.
- ``FitResult.params`` is ``[batch?, k]``; per-series diagnostics (converged,
  iterations, final objective) ride along — the structured-diagnostics
  replacement for Spark logs (SURVEY.md Section 5.5).
"""

from __future__ import annotations

import functools
import threading
import weakref
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import obs


def jit_program(builder):
    """Build + cache ONE compiled program per static configuration.

    ``builder(*static) -> traceable fn``; ``jit_program(builder)(*static)``
    returns the jitted fn, cached on the static args.  Model entry points are
    plain library calls (no long-lived jit closure at the call site), so
    without this every ``fit``/``forecast`` call would re-trace and
    re-compile — the analog of the reference reusing one JVM JIT-compiled
    code path across calls.

    Every lookup reports hit/miss to ``utils.compile_cache`` (obs counters
    ``compile_cache.hit`` / ``compile_cache.miss``): per-order program
    reuse is the auto-fit search's perf core (ISSUE 9), and the hit rate
    makes that reuse measurable instead of assumed.

    The built function takes its BUILDER's qualified name
    (``arima._fit_stage1_program``) before ``jax.jit``: most builders
    return a closure called ``run``, and jax names a program — its HLO
    module, and the ``program`` of ``compile_cache``'s build log — after
    the function it was handed.  The name is no part of the dataflow.
    """
    name = f"{builder.__module__.rpartition('.')[2]}.{builder.__qualname__}"

    def build(*static):
        fn = builder(*static)
        fn.__name__ = name
        return jax.jit(fn)

    cached = functools.lru_cache(maxsize=512)(build)
    # lookup + hit/miss classification are one atomic step: sharded lane
    # threads call fit concurrently, and an unsynchronized cache_info()
    # delta would misattribute another thread's hit to this thread's
    # miss, making the published reuse rate nondeterministic.  The lock
    # only guards building the (cheap, uncompiled) jitted wrapper — XLA
    # compilation happens at first dispatch, outside it.
    lock = threading.Lock()

    def norm(a):  # tolerate list-valued order/shape args (lists don't hash)
        return tuple(a) if isinstance(a, list) else a

    def get(*static):
        from ..utils import compile_cache as _cc

        _cc.listen()  # the build log: whatever this program compiles to
        with lock:
            before = cached.cache_info().hits
            out = cached(*map(norm, static))
            hit = cached.cache_info().hits > before
        (_cc.note_hit if hit else _cc.note_miss)()
        return out

    return functools.wraps(builder)(get)


def resolve_backend(backend: str, dtype, n_time: int,
                    structural_ok: bool = True) -> str:
    """Validate a fit ``backend`` and resolve ``"auto"``.

    ``auto`` picks the fused Pallas objective when the platform/dtype/length
    allow (``ops.pallas_kernels.supported``) AND the model's structural
    parameters fit the kernel's chunked layout (``structural_ok`` — e.g.
    ``pk.css_structural_ok(p, q)``), else the portable ``lax.scan`` path.
    An explicitly requested ``"pallas"`` with violating structure raises at
    the kernel entry point instead, and one the platform or dtype cannot
    run natively is refused here.  Shared by every model family so the
    backend vocabulary cannot drift between them.
    """
    if backend not in ("auto", "scan", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend in ("scan", "pallas-interpret"):
        return backend
    from ..ops import pallas_kernels as pk

    native = pk.supported(dtype, n_time)
    if backend == "pallas":
        if not native:
            raise ValueError(
                "backend='pallas' runs the fused kernels natively: float32 "
                "on a TPU; elsewhere use 'pallas-interpret' or the scan "
                "backend")
        return backend
    return "pallas" if structural_ok and native else "scan"


class FitResult(NamedTuple):
    """Batched fit output: parameters + convergence diagnostics.

    ``status`` carries per-row ``reliability.FitStatus`` codes (int8): a
    plain fit reports ``OK`` (converged, finite params), ``DIVERGED``
    (optimizer failed or produced non-finite output), or ``EXCLUDED``
    (the model rejected the row structurally — too short / all NaN).  The
    resilient runner (``reliability.resilient_fit``) refines these with
    the ``SANITIZED`` / ``RETRIED`` / ``FALLBACK`` transitions.
    """

    params: jax.Array  # [batch?, k]
    neg_log_likelihood: jax.Array  # [batch?] final objective (model-defined)
    converged: jax.Array  # [batch?] bool
    iters: jax.Array  # [batch?] optimizer iterations used
    status: jax.Array = None  # [batch?] int8 FitStatus codes


def derive_status(ok, converged, params) -> jax.Array:
    """Per-row FitStatus for a plain (non-resilient) fit program.

    ``ok`` is the model's structural gate (enough valid observations to
    identify the parameters): gated-out rows are ``EXCLUDED``; rows that
    converged to finite params are ``OK``; everything else ``DIVERGED``.
    Computed inside the jitted fit program — int8 codes cost nothing next
    to the params they ride with.
    """
    from ..reliability.status import FitStatus

    good = ok & converged & jnp.all(jnp.isfinite(params), axis=-1)
    return jnp.where(
        ~ok,
        jnp.int8(FitStatus.EXCLUDED),
        jnp.where(good, jnp.int8(FitStatus.OK), jnp.int8(FitStatus.DIVERGED)),
    )


def ensure_batched(y) -> tuple[jax.Array, bool]:
    """Promote ``[time]`` to ``[1, time]``; report whether input was single."""
    y = jnp.asarray(y)
    if y.ndim == 1:
        return y[None, :], True
    if y.ndim == 2:
        return y, False
    raise ValueError(f"series must be [time] or [batch, time], got {y.shape}")


def debatch(x, single: bool):
    return jax.tree.map(lambda a: a[0], x) if single else x


def require_pallas_for_count_evals(count_evals: bool, backend: str) -> None:
    """Shared ``count_evals`` contract: pass accounting instruments the
    batched L-BFGS (``utils.optim``), which only the pallas fit paths use —
    the scan paths go through ``batched_minimize`` (vmapped per-series
    loops) where a per-iteration eval count has no batched meaning."""
    if count_evals and backend not in ("pallas", "pallas-interpret"):
        raise ValueError("count_evals requires the pallas backend "
                         f"(resolved backend: {backend!r})")


def debatch_fit(out, single: bool, count_evals: bool):
    """Unpack a fit program's ``result | (result, info)`` return shape."""
    if count_evals:
        res, info = out
        return debatch(res, single), info
    return debatch(out, single)


ALIGN_MODES = ("dense", "no-trailing", "general")


def resolve_align_mode(yb, align_mode: Optional[str] = None) -> str:
    """Resolve a fit's static alignment mode: caller hint or host probe.

    ``align_mode=None`` (the default) probes the panel on the host
    (:func:`align_mode_on_host` — one fused reduction + one host sync,
    cached per array identity).  A non-None hint skips the probe and the
    sync entirely: the chunk driver (``reliability.fit_chunked``) computes
    the panel's mode ONCE per walk and threads it into every chunk fit as
    a static argument, so a sliced walk pays zero per-chunk probe syncs.

    **Hint contract** (wrong hint = flagged rows, never silently wrong
    numbers): an unknown mode name raises ``ValueError``; a WEAKER mode
    than the data needs (``"general"`` on a dense panel) is always
    numerically correct, only slower; a STRONGER mode than the data
    supports surfaces per row — under ``"dense"`` any NaN poisons that
    row's objective (``converged=False``, status ``DIVERGED``), and under
    ``"no-trailing"`` a row whose last position is NaN is excluded
    (``n_valid=0``, NaN params, status ``EXCLUDED``) by the guard in
    :func:`maybe_align` rather than fitted against a zero-filled tail
    with an inflated valid span.
    """
    if align_mode is None:
        return align_mode_on_host(yb)
    if align_mode not in ALIGN_MODES:
        raise ValueError(
            f"unknown align_mode {align_mode!r} (one of {ALIGN_MODES})")
    return align_mode


def align_mode_on_host(yb) -> str:
    """Static alignment mode for a fit program: how much work the per-row
    right-alignment actually needs on THIS panel.

    - ``"dense"``: no NaNs anywhere — alignment is the identity.
    - ``"no-trailing"``: every series is valid at the last position, so the
      valid span already ENDS at T-1 (leading-NaN ragged series, the common
      different-start-dates panel): alignment is just prefix zeroing —
      no roll.
    - ``"general"``: trailing NaNs exist somewhere — the full per-row roll.

    Decided OUTSIDE the jitted program because the roll is the expensive
    part: vmapped ``jnp.roll`` lowers to a batched gather that costs more at
    panel scale (~0.4 s at 100k x 1k) than the entire L-BFGS loop.  The
    check is one fused reduction + one host sync — paid ONCE per array:
    jax arrays are immutable, so the mode is cached per array identity
    (a weakref guards against id reuse after GC), and repeated un-jitted
    ``fit``/``forecast`` calls on the same panel skip the device round-trip
    (VERDICT r3 item 9).  Traced inputs (``fit`` called under jit) can't be
    inspected and take the general path.
    """
    if isinstance(yb, jax.core.Tracer):
        return "general"
    key = id(yb)
    with _align_mode_lock:
        hit = _align_mode_cache.get(key)
    if hit is not None and hit[0]() is yb:
        return hit[1]
    # each probe is a device round-trip (host sync); counted so drivers can
    # verify a sliced chunk walk really paid ONE probe, not one per chunk
    obs.counter("align.host_probes").inc()
    nan_any, nan_last = _nan_probe(yb)
    if not bool(nan_any):
        mode = "dense"
    else:
        mode = "no-trailing" if not bool(nan_last) else "general"
    try:
        ref = weakref.ref(yb)
    except TypeError:  # not weak-referenceable (e.g. plain numpy scalarlike)
        return mode
    with _align_mode_lock:
        if len(_align_mode_cache) >= 256:
            # drop entries whose array has been collected first; only if
            # the cache is genuinely full of LIVE arrays fall back to FIFO
            # eviction of the oldest insertions (dicts preserve insertion
            # order) — a process cycling many panels must not lose every
            # cached mode at once (ADVICE r4)
            dead = [k for k, (r, _) in _align_mode_cache.items()
                    if r() is None]
            for k in dead:
                del _align_mode_cache[k]
            while len(_align_mode_cache) >= 256:
                del _align_mode_cache[next(iter(_align_mode_cache))]
        _align_mode_cache[key] = (ref, mode)
    return mode


_align_mode_cache: dict = {}  # id(array) -> (weakref, mode)
# fits run on several threads of one process (sharded lanes, a lane's chunk
# fitted ahead): the eviction scan must not meet another thread's insert
_align_mode_lock = threading.Lock()


@jax.jit  # module-level: one compile per shape, not per call
def _nan_probe(v):
    return jnp.any(jnp.isnan(v)), jnp.any(jnp.isnan(v[:, -1]))


def maybe_align(yb, mode: str):
    """``(aligned, n_valid)`` under a static :func:`align_mode_on_host` mode."""
    if mode == "dense":
        return yb, jnp.full((yb.shape[0],), yb.shape[1], jnp.int32)
    if mode == "no-trailing":
        valid = ~jnp.isnan(yb)
        # interior NaNs are zero-filled exactly as align_right does
        first = jnp.argmax(valid, axis=1)
        nv = yb.shape[1] - first
        t = jnp.arange(yb.shape[1])[None, :]
        ya = jnp.where(t >= first[:, None], jnp.nan_to_num(yb), 0.0)
        # hint guard (resolve_align_mode contract): a row whose LAST
        # position is NaN violates "no-trailing" — exclude it (n_valid=0,
        # NaN values) instead of silently fitting a zero-filled tail with
        # an inflated valid span.  The host probe never derives this mode
        # when such rows exist, so on probe-derived panels ``bad`` is
        # all-False and the select is numerically a no-op; one column read
        # is the entire cost of making a wrong caller hint loud.
        bad = jnp.isnan(yb[:, -1])
        ya = jnp.where(bad[:, None], jnp.nan, ya)
        nv = jnp.where(bad, 0, nv)
        return ya, nv.astype(jnp.int32)
    ya, nv = jax.vmap(align_right)(yb)
    return ya, nv.astype(jnp.int32)


def align_right(y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Shift a series' valid span to END at the last position -> ``(y', n_valid)``.

    Model fits accept series with leading/trailing NaNs (unobserved head or
    tail — the ragged-panel case of SURVEY.md §7): the valid run
    ``[first_non_nan, last_non_nan]`` is rolled so it ends at ``T-1``, padding
    positions become 0.0, and ``n_valid`` (its length, traced scalar) lets
    objectives mask the padded prefix while every shape stays static.  With
    the data right-aligned, "last value" / "last errors" logic in forecasting
    needs no dynamic indexing.

    Interior NaNs inside the valid run are replaced by 0.0 — fill them first
    (``panel.fill``) for meaningful fits.  All-NaN input yields ``n_valid=0``
    (callers flag such series as failed).
    """
    y = jnp.asarray(y)
    T = y.shape[0]
    valid = ~jnp.isnan(y)
    any_valid = jnp.any(valid)
    first = jnp.argmax(valid)
    last = T - 1 - jnp.argmax(valid[::-1])
    nv = jnp.where(any_valid, last - first + 1, 0)
    rolled = jnp.roll(y, (T - 1) - last)
    t = jnp.arange(T)
    rolled = jnp.where(t >= T - nv, rolled, 0.0)
    return jnp.nan_to_num(rolled), nv
