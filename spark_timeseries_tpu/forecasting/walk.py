"""The chunked forecast walk: panel-scale forecasts on the fit driver.

The forecast of a panel is EMBARRASSINGLY parallel — every row's future
depends only on its own history and its own fitted params — so instead
of a new execution engine, the walk reuses ``reliability.fit_chunked``
wholesale: the per-row side data (params, status, row index) is packed
into extra panel columns (:mod:`.augment`), and :func:`forecast_fit` —
an ordinary chunk "fit function" returning a ``FitResult`` whose params
matrix IS the packed ``[point | lo | hi]`` forecast block — rides the
driver.  Journaling (SIGKILL-resume replaying only uncommitted chunks),
pipelined commits, dispatch-ahead prefetch, ``ChunkSource`` streaming,
mesh sharding, and elastic lanes therefore compose with the forecast
path for free, and the composed walks are bitwise-identical to the
serial in-memory walk ON THE SAME CHUNK GRID: the forecast kernels are
row-local vmapped programs with no cross-row coupling, staged chunks are
the same bytes in every residency, shard boundaries land on chunk
boundaries, and the interval sampling keys are counter-based on the
GLOBAL row index (``fold_in(base_key, row)``), never on chunk shape.
(Like the fits, low-order bits can follow the chunk SHAPE — XLA
reduction order inside a row's sigma estimate is batch-size-dependent —
so cross-grid comparisons are value-close, not bitwise; every driver
composition keeps the grid fixed.)

**Status propagation**: a row whose fit did not produce usable params
(status ``DIVERGED``/``EXCLUDED``/``TIMEOUT``, or non-finite params)
forecasts NaN — never garbage — and keeps its fit status in the result;
healthy rows (including ``SANITIZED``/``RETRIED``/``FALLBACK`` rescues)
forecast from their params and keep their provenance code.

**Reproducible intervals**: ``intervals=True`` adds Monte-Carlo
``level``-quantile bands from each model's forward simulation
(:mod:`.kernels`), under a base key derived deterministically from the
augmented panel's JOURNAL FINGERPRINT (or an explicit ``seed``) — the
same panel + params forecast the same bands on every run, resume, chunk
layout, and shard count, bitwise.
"""

from __future__ import annotations

import hashlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..models.base import FitResult, jit_program
from ..reliability import source as source_mod
from ..reliability.journal import panel_fingerprint
from ..reliability.runner import ResilientFitResult
from ..reliability.status import FitStatus, status_counts
from . import augment, kernels
from .params import load_fit_result

__all__ = ["ForecastResult", "forecast_chunked", "forecast_fit",
           "split_forecast", "warmstart_fit"]


class ForecastResult(NamedTuple):
    """Panel forecast output: rows align with the input panel.

    ``forecast`` is ``[B, horizon]`` point forecasts (NaN for rows whose
    fit was unusable); ``lo``/``hi`` the interval bands (None without
    ``intervals=True``); ``status`` the propagated per-row fit status;
    ``meta`` the walk accounting (``meta["forecast"]`` the forecast
    config, plus everything the chunk driver reports — journal, pipeline
    overlap, shards, source staging).
    """

    forecast: np.ndarray  # [B, horizon]
    lo: Optional[np.ndarray]  # [B, horizon] or None
    hi: Optional[np.ndarray]  # [B, horizon] or None
    status: np.ndarray  # [B] int8 FitStatus
    meta: dict


def split_forecast(pack: np.ndarray, horizon: int, intervals: bool):
    """Unpack a walk's params matrix ``[B, W]`` into (point, lo, hi).

    Tolerates the all-TIMEOUT degenerate pack (the driver synthesizes
    width-1 NaN params when no chunk ever finished)."""
    pack = np.asarray(pack)
    b = pack.shape[0]
    want = horizon * (3 if intervals else 1)
    if pack.shape[1] != want:
        nanmat = np.full((b, horizon), np.nan, pack.dtype)
        return (nanmat, nanmat.copy() if intervals else None,
                nanmat.copy() if intervals else None)
    point = np.array(pack[:, :horizon])
    if not intervals:
        return point, None, None
    return (point, np.array(pack[:, horizon:2 * horizon]),
            np.array(pack[:, 2 * horizon:3 * horizon]))


def forecast_fit(aug, *, forecast_model, horizon, n_time, k,
                 model_kwargs=(), intervals=False, level=0.9,
                 n_samples=256, base_seed=0):
    """The forecast walk's chunk fit function.

    ``aug`` is an augmented-panel chunk (``.augment`` layout); the
    statics select ONE compiled program per configuration
    (``forecast_model`` names the model family — spelled distinctly from
    the serving layer's ``model`` registry-name parameter so the config
    rides ``FitServer.submit`` untouched).  Returns a ``FitResult``
    whose ``params`` is the packed forecast block — which is exactly
    what the journal commits and a resume rehydrates.  Run it through
    ``fit_chunked(..., resilient=False)``: the resilient ladder must
    never "sanitize" a panel whose columns are fitted parameters.
    """
    mk = kernels.normalize_model_kwargs(str(forecast_model),
                                        dict(model_kwargs))
    return _forecast_chunk_program(
        str(forecast_model), mk, int(horizon), int(n_time), int(k),
        bool(intervals), float(level), int(n_samples), int(base_seed),
    )(jnp.asarray(aug))


@jit_program
def _forecast_chunk_program(model, mk, horizon, n_time, k, intervals,
                            level, n_samples, base_seed):
    cfg = dict(mk)
    want_k = kernels.param_width(model, cfg)
    if want_k != k:
        raise ValueError(
            f"model {model!r} with config {cfg} expects {want_k} params "
            f"per row, augmented panel carries {k}")
    point_f = kernels.point_fn(model, cfg, horizon)
    sim_f = (kernels.sim_fn(model, cfg, horizon, n_samples)
             if intervals else None)

    def run(aug):
        y = aug[:, :n_time]
        params = aug[:, n_time:n_time + k]
        status = aug[:, n_time + k].astype(jnp.int8)
        usable = (jnp.all(jnp.isfinite(params), axis=-1)
                  & (status < jnp.int8(FitStatus.DIVERGED)))
        point = jnp.where(usable[:, None], point_f(params, y), jnp.nan)
        blocks = [point]
        if intervals:
            rowidx = aug[:, n_time + k + 1].astype(jnp.int32)
            key0 = jax.random.PRNGKey(base_seed)
            keys = jax.vmap(lambda r: jax.random.fold_in(key0, r))(rowidx)
            paths = sim_f(params, y, keys)  # [B, S, H]
            ql = (1.0 - level) / 2.0
            lo = jnp.quantile(paths, ql, axis=1)
            hi = jnp.quantile(paths, 1.0 - ql, axis=1)
            blocks += [jnp.where(usable[:, None], lo, jnp.nan),
                       jnp.where(usable[:, None], hi, jnp.nan)]
        pack = jnp.concatenate(blocks, axis=1).astype(aug.dtype)
        nll = jnp.where(usable, 0.0, jnp.nan).astype(aug.dtype)
        return FitResult(pack, nll, usable,
                         jnp.zeros(aug.shape[0], jnp.int32), status)

    return run


def warmstart_fit(aug, *, model, n_time, k, model_kwargs=()):
    """Chunk fit function for a WARM-STARTED refit walk (the backtest
    campaign's expanding windows): the augmented panel carries
    ``[y (n_time) | init params (k)]`` and the model fits with
    ``init_params`` taken from the extra columns — per-chunk, so the
    warm start rides any chunking/sharding/streaming, exactly like the
    forecast pack.  Non-finite inits (a failed previous-window row) are
    zeroed, the model's own cold-ish default, mirroring the winners
    refit (``models.auto._refit_basin``; a GARCH row at ``omega`` 0 is no
    GARCH point and takes ``garch.fit``'s moment start).  Run with
    ``resilient=False``: the sanitizer must not touch param columns.
    """
    from ..models import arima as _arima
    from ..models import garch as _garch

    cfg = dict(model_kwargs)
    aug = jnp.asarray(aug)
    y = aug[:, :int(n_time)]
    init = aug[:, int(n_time):int(n_time) + int(k)]
    init = jnp.where(jnp.isfinite(init), init, 0.0)
    if model == "arima":
        order = tuple(cfg.pop("order"))
        return _arima.fit(y, order=order, init_params=init, **cfg)
    if model == "garch":
        return _garch.fit(y, init_params=init, **cfg)
    raise ValueError(
        f"warm-started refits need a fit with init_params= "
        f"(arima family, garch); got {model!r}")


def _derive_base_seed(fingerprint: str) -> int:
    digest = hashlib.sha256(
        ("ststpu-forecast:" + fingerprint).encode()).digest()
    return int.from_bytes(digest[:4], "big") & 0x7FFFFFFF


def forecast_chunked(
    model: str,
    fitted,
    y,
    horizon: int,
    *,
    model_kwargs: Optional[dict] = None,
    status=None,
    intervals: bool = False,
    level: float = 0.9,
    n_samples: int = 256,
    seed: Optional[int] = None,
    chunk_rows: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    resume: str = "auto",
    chunk_budget_s: Optional[float] = None,
    job_budget_s: Optional[float] = None,
    pipeline: bool = True,
    pipeline_depth: int = 2,
    prefetch_depth: int = 1,
    shard: bool = False,
    mesh=None,
    sink=None,
    _journal_commit_hook=None,
) -> ForecastResult:
    """Forecast ``horizon`` steps for every row of ``y [B, T]``.

    ``fitted`` supplies the per-row parameters: an in-memory fit result
    (anything with ``params`` [+ ``status``] — ``FitResult``,
    ``ResilientFitResult``, ``TenantFitResult``), a raw ``[B, k]``
    params array, or a STRING path to a fit walk's journal directory
    (fit-once on disk -> forecast-many later: the journal is assembled
    host-side via :func:`.params.load_fit_result`, committed rows byte
    identical to the original walk's output).  ``status`` overrides the
    per-row fit status (default: taken from ``fitted``, or derived from
    params finiteness) and gates NaN propagation.

    ``y`` is a device/host array or any ``ChunkSource`` (the augmented
    panel then STREAMS — an oversubscribed panel forecasts at O(chunk)
    device footprint).  All the chunk driver's knobs ride through —
    ``checkpoint_dir`` journals the walk (forecast shards resume
    bitwise), ``shard=True`` runs one elastic lane per mesh device,
    pipeline/prefetch overlap staging and commits — and every
    composition is bitwise-identical to the serial in-memory walk.

    ``intervals=True`` adds ``level`` Monte-Carlo quantile bands
    (``n_samples`` forward simulations/row) under a base key derived
    from the augmented panel's journal fingerprint (``seed`` overrides),
    so bands are bitwise-reproducible across runs, resumes, shards, and
    residencies on the same chunk grid.
    """
    horizon = int(horizon)
    if horizon < 1:
        raise ValueError(f"horizon must be >= 1, got {horizon}")
    mk = kernels.normalize_model_kwargs(model, model_kwargs or {})
    cfg = dict(mk)
    if isinstance(fitted, str):
        fitted = load_fit_result(fitted)
    if hasattr(fitted, "order_index"):
        # an auto-fit selection packs each ROW's params in its own
        # winning order's layout — reading them under one fixed order
        # would forecast finite garbage with status OK for every row
        # whose winner differs (the exact never-garbage violation this
        # walk exists to prevent)
        raise ValueError(
            "an auto-fit selection mixes parameter layouts per row "
            "(each row's winning order); forecast it with "
            "forecasting.ensemble_forecast(auto_root=..., "
            "temperature=0) — per-order walks + a per-row winner "
            "gather — not a single-order forecast")
    if hasattr(fitted, "params"):
        params = np.asarray(fitted.params)
        if status is None:
            status = getattr(fitted, "status", None)
    else:
        params = np.asarray(fitted)
    if params.ndim != 2:
        raise ValueError(f"params must be [rows, k], got {params.shape}")
    k = kernels.param_width(model, cfg)
    if params.shape[1] < k:
        raise ValueError(
            f"model {model!r} with config {cfg} needs {k} params per "
            f"row, fitted carries {params.shape[1]}")
    params = np.ascontiguousarray(params[:, :k])
    st = augment.derive_status(params, status)
    aug, n_time, k = augment.augmented_panel(y, params, st)

    base_seed = 0
    if intervals:
        if seed is not None:
            base_seed = int(seed)
        else:
            fp = (aug.fingerprint()
                  if isinstance(aug, source_mod.ChunkSource)
                  else panel_fingerprint(aug))
            base_seed = _derive_base_seed(fp)

    from ..reliability import fit_chunked

    journal_extra = {"forecast": {
        "model": model, "horizon": int(horizon),
        "n_time": int(n_time), "k": int(k),
        "model_kwargs": {key: (list(v) if isinstance(v, tuple) else v)
                         for key, v in cfg.items()},
        "intervals": bool(intervals),
        "level": float(level) if intervals else None,
        "n_samples": int(n_samples) if intervals else None,
        "base_seed": int(base_seed) if intervals else None,
    }}
    with obs.span("panel.forecast", model=model, horizon=int(horizon),
                  n_series=int(params.shape[0])):
        res = fit_chunked(
            forecast_fit, aug,
            chunk_rows=chunk_rows,
            resilient=False,
            checkpoint_dir=checkpoint_dir, resume=resume,
            chunk_budget_s=chunk_budget_s, job_budget_s=job_budget_s,
            pipeline=pipeline, pipeline_depth=pipeline_depth,
            prefetch_depth=prefetch_depth,
            shard=shard, mesh=mesh, sink=sink,
            journal_extra=journal_extra,
            _journal_commit_hook=_journal_commit_hook,
            # -- the forecast config (all hashed into the journal id) --
            forecast_model=model, horizon=int(horizon),
            n_time=int(n_time), k=int(k), model_kwargs=mk,
            intervals=bool(intervals), level=float(level),
            n_samples=int(n_samples), base_seed=int(base_seed),
        )
    if res.params is None:
        # write-back mode (ISSUE 20): the packed forecasts streamed out
        # as durable output shards under key "params"; read them back at
        # O(chunk) footprint with NpzShardSource(sink_dir, key="params")
        # and split_forecast.  meta["sink"] carries the accounting and
        # meta["status_counts"] the per-row outcome totals.
        meta = dict(res.meta)
        meta["forecast"] = {**journal_extra["forecast"],
                            "status_counts": res.meta["status_counts"]}
        obs.counter("forecast.walks").inc()
        return ForecastResult(None, None, None, None, meta)
    point, lo, hi = split_forecast(res.params, int(horizon),
                                   bool(intervals))
    out_status = np.asarray(res.status, np.int8)
    meta = dict(res.meta)
    meta["forecast"] = {**journal_extra["forecast"],
                        "status_counts": status_counts(out_status)}
    obs.counter("forecast.walks").inc()
    return ForecastResult(point, lo, hi, out_status, meta)


def as_result(res: ResilientFitResult, horizon: int,
              intervals: bool) -> ForecastResult:
    """Wrap a raw forecast-walk fit result (e.g. a serving demux slice)
    into a :class:`ForecastResult`."""
    point, lo, hi = split_forecast(res.params, int(horizon),
                                   bool(intervals))
    return ForecastResult(point, lo, hi,
                          np.asarray(res.status, np.int8),
                          dict(getattr(res, "meta", {}) or {}))
