"""Input sanitization pass: repair or reject rows a batched fit cannot survive.

The reference could lean on per-series JVM exceptions — one NaN-laced series
threw inside its own executor task and Spark retried or dropped that task.
A monolithic vmapped fit has no such isolation: every row shares one
program, so bad input must be found and neutralized BEFORE the fit.  Models
already tolerate leading/trailing NaNs (the ragged-panel contract,
``models.base.align_right``); what they cannot tolerate is

- ``inf``/``-inf`` anywhere (squares overflow, gradients go non-finite),
- NaN *inside* the valid span (``align_right`` zero-fills them, silently
  biasing the fit),
- constant rows (zero innovation variance -> ``log(0)`` objectives), and
- all-NaN rows (nothing to fit).

:func:`sanitize` detects all four with one fused device pass and applies a
configurable policy, emitting a per-row :class:`~.status.FitStatus` code.
Rows it does not touch are returned BIT-IDENTICAL, so healthy rows fit
exactly as they would have without the pass.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..ops import univariate as uv
from .status import STATUS_DTYPE, FitStatus

POLICIES = ("impute", "exclude", "raise")


class SanitizeReport(NamedTuple):
    """Output of :func:`sanitize`."""

    values: jax.Array  # [B, T] cleaned panel (untouched rows bit-identical)
    status: np.ndarray  # [B] int8: OK / SANITIZED / EXCLUDED
    flags: dict  # per-row bool masks: had_inf / interior_nan / constant / all_nan
    meta: dict  # summary counts for result metadata


@jax.jit  # module-level: one compile per panel shape
def _probe(yb):
    """One fused pass: per-row fault masks (no repair work — the fill runs
    in :func:`_impute` only when a repairable row actually exists, so the
    clean-panel hot path pays masks-only)."""
    t = jnp.arange(yb.shape[1])[None, :]
    had_inf = jnp.any(jnp.isinf(yb), axis=1)
    y1 = jnp.where(jnp.isinf(yb), jnp.nan, yb)  # non-inf entries bit-identical
    valid = ~jnp.isnan(y1)
    any_valid = jnp.any(valid, axis=1)
    first = jnp.argmax(valid, axis=1)
    last = yb.shape[1] - 1 - jnp.argmax(valid[:, ::-1], axis=1)
    inside = (t >= first[:, None]) & (t <= last[:, None])
    interior_nan = jnp.any(inside & ~valid, axis=1)
    hi = jnp.max(jnp.where(valid, y1, -jnp.inf), axis=1)
    lo = jnp.min(jnp.where(valid, y1, jnp.inf), axis=1)
    constant = any_valid & (hi == lo)
    return y1, had_inf, interior_nan, constant, ~any_valid


@jax.jit
def _impute(y1, repair_mask):
    """Linear-fill interior gaps of the flagged rows (others bit-identical)."""
    filled = jax.vmap(uv.fill_linear)(y1)  # interior gaps only; edges stay NaN
    return jnp.where(repair_mask[:, None], filled, y1)


def sanitize(y, policy: str = "impute") -> SanitizeReport:
    """Detect and handle non-finite / degenerate rows of a ``[B, T]`` panel.

    ``policy`` governs rows with repairable faults (inf entries or NaNs
    inside the valid span):

    - ``"impute"``: inf -> NaN, interior NaNs linearly interpolated
      (``ops.univariate.fill_linear``); the row is flagged ``SANITIZED``.
    - ``"exclude"``: the row is replaced by all-NaN (models return NaN
      params for it without touching its neighbors) and flagged
      ``EXCLUDED``.
    - ``"raise"``: a ``ValueError`` naming the offending rows.

    Constant and all-NaN rows are unrepairable (no innovation variance /
    nothing to fit): they are excluded under both non-raising policies.
    Leading/trailing NaNs alone are NOT faults — ragged panels pass
    through untouched (the ``align_right`` contract).
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown sanitize policy {policy!r} (one of {POLICIES})")
    yb = jnp.asarray(y)
    if yb.ndim != 2:
        raise ValueError(f"sanitize expects [batch, time], got {yb.shape}")
    with obs.span("sanitize", rows=int(yb.shape[0]), policy=policy):
        return _sanitize_timed(yb, policy)


def _sanitize_timed(yb, policy: str) -> SanitizeReport:
    y1, had_inf, interior_nan, constant, all_nan = _probe(yb)
    # the four masks come over together, not in four round trips one after
    # another (1 ms each behind a busy device: the next dispatch waits for
    # the last of them)
    for mask in (had_inf, interior_nan, constant, all_nan):
        mask.copy_to_host_async()
    had_inf = np.asarray(had_inf)
    interior_nan = np.asarray(interior_nan)
    constant = np.asarray(constant)
    all_nan = np.asarray(all_nan)

    repairable = had_inf | interior_nan
    unusable = constant | all_nan
    if policy == "raise" and (repairable | unusable).any():
        bad = np.nonzero(repairable | unusable)[0]
        raise ValueError(
            f"{bad.size} rows failed sanitization (policy='raise'), e.g. rows "
            f"{bad[:5].tolist()}: inf={int(had_inf.sum())}, "
            f"interior NaN={int(interior_nan.sum())}, "
            f"constant={int(constant.sum())}, all-NaN={int(all_nan.sum())}"
        )

    status = np.zeros(yb.shape[0], STATUS_DTYPE)
    if policy == "impute":
        excluded = unusable
        status[repairable & ~excluded] = FitStatus.SANITIZED
    else:  # exclude
        excluded = unusable | repairable
    status[excluded] = FitStatus.EXCLUDED

    out = y1
    if policy == "impute" and repairable.any():
        out = _impute(out, jnp.asarray(repairable))
    if excluded.any():
        out = jnp.where(jnp.asarray(excluded)[:, None], jnp.nan, out)

    flags = {
        "had_inf": had_inf,
        "interior_nan": interior_nan,
        "constant": constant,
        "all_nan": all_nan,
    }
    meta = {
        "policy": policy,
        "rows_sanitized": int((status == FitStatus.SANITIZED).sum()),
        "rows_excluded": int((status == FitStatus.EXCLUDED).sum()),
        **{f"rows_{k}": int(v.sum()) for k, v in flags.items()},
    }
    # telemetry: sanitizer actions as monotonic counters (no-ops when off)
    obs.counter("sanitize.rows_checked").add(int(yb.shape[0]))
    obs.counter("sanitize.rows_sanitized").add(meta["rows_sanitized"])
    obs.counter("sanitize.rows_excluded").add(meta["rows_excluded"])
    for k, v in flags.items():
        n = int(v.sum())
        if n:
            obs.counter(f"sanitize.rows_{k}").add(n)
    return SanitizeReport(out, status, flags, meta)
