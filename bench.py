"""Benchmark harness: all five BASELINE configs + a measured CPU baseline.

Emits ONE JSON line per benchmark, each with the driver schema
``{"metric", "value", "unit", "vs_baseline"}`` plus extra diagnostic fields.
The HEADLINE line (config 3, the north-star ARIMA fit) prints after the
other configs, followed only by a compact ``bench_summary`` digest of every
config — the driver artifact keeps just the output TAIL (~2000 chars, which
by round 5 no single full config line fit inside), so the digest is what
guarantees the artifact captures every config's numbers
(``tools/gen_readme_perf.py`` parses it first-class).

Configs (``BASELINE.json.configs``):
  1. autocorr via the mapSeries equivalent, 1k keys x 1k obs
  2. fillLinear + lag/difference batched ops, 100k keys x 1k obs
  3. ARIMA(1,1,1) fit + forecast, 100k keys x 1k obs   <- headline
  4. GARCH(1,1) fit on a daily-returns panel, 50k tickers x 1k obs
  5. Holt-Winters additive (period 24), 1M hourly series x 960 obs

CPU baseline (the reference publishes no numbers — BASELINE.md): measured
here with faithful single-core oracles.  The sequential recursions (ARIMA
CSS, GARCH variance) run at C speed via ``scipy.signal.lfilter`` — the
honest stand-in for the reference's compiled JVM/Breeze loops — driven by
``scipy.optimize`` L-BFGS-B exactly where the reference drives Commons-Math
optimizers; autocorr/fill are vectorized numpy.  Holt-Winters has no
lfilter form (three coupled carries + a seasonal ring); its oracle is a
batch-vectorized numpy recursion (serial in t, whole batch per step)
driven by FD gradient descent, flagged in its metric string.  All-core
rates are the single-core
rate times ``os.cpu_count()`` (the workload is embarrassingly parallel
across series — the same assumption Spark's per-partition loops make).

``vs_baseline`` semantics:
  - config 3: throughput / (100k series/sec * n_chips/8) — the pro-rated
    north-star target; ``vs_target_unscaled`` carries the raw /100k ratio.
  - configs 1/2/4/5: measured speedup over the ALL-CORE CPU oracle divided
    by the 30x north-star speedup target, so > 1.0 beats the target.

Convergence honesty (VERDICT round 1): the headline fit runs the library
default optimizer budget and reports the converged fraction and converged-
only throughput; before any timing, the fused Pallas objective is checked
against the portable scan objective on-device (native lowering parity).

Usage: ``python bench.py [--configs 1,2,3,4,5] [--quick] [--profile DIR]``
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np


NORTH_STAR = 100_000.0  # series/sec, config 3, v5e-8
SPEEDUP_TARGET = 30.0  # vs CPU baseline
CPU_BUDGET_S = 30.0  # max wall time per CPU oracle measurement
# HBM bandwidth by jax ``device_kind`` (roofline denominator).  Source:
# Google Cloud documentation, "TPU v5e": 819 GB/s.  A device that is not
# listed is an error, not a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def _hbm_peak_gbps(device=None):
    """HBM peak of ``device`` (default: the first jax device); ``None`` on
    the CPU — the ``--quick`` control-flow smoke has no HBM and reports no
    roofline share."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    if device.device_kind not in HBM_PEAK_GBPS:
        raise RuntimeError(
            f"no HBM peak recorded for device_kind {device.device_kind!r}; "
            "add it to bench.HBM_PEAK_GBPS with its source")
    return HBM_PEAK_GBPS[device.device_kind]


def _marginal(run_k, run_1, k, b, actual_bytes_per_panel, reps=12):
    """Dispatch-cost-free device throughput (VERDICT r3 item 2): the
    K-panel dispatch minus the structurally identical 1-panel dispatch,
    divided by K-1, cancels the fixed per-dispatch host cost.

    PAIRED interleaved timing: the two programs alternate and the MEDIAN of
    per-pair differences is used, so slow host drift cancels and a single
    jitter spike cannot set the estimate.  A physics clamp rejects draws
    that would imply the program streamed its actual traffic above HBM
    peak — such a "measurement" is jitter, not throughput — returning
    ``(None, None)`` instead of an absurd rate."""
    tks, t1s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_k()
        tks.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_1()
        t1s.append(time.perf_counter() - t0)
    diffs = [a - c for a, c in zip(tks, t1s)]
    # two estimators, take the more CONSERVATIVE (larger) one: the median of
    # paired diffs (drift-cancelling) and the difference of per-program
    # floors (spike-resistant); min-of-diffs is biased fast and not used
    per = max(float(np.median(diffs)), min(tks) - min(t1s)) / (k - 1)
    peak = _hbm_peak_gbps()
    if per <= 0 or (peak is not None
                    and actual_bytes_per_panel / per > 1.1 * peak * 1e9):
        return None, None
    return per, b / per


def _roofline(bytes_moved, seconds):
    """Roofline accounting for a memory-bound transform (VERDICT r3 item 2).

    ``bytes_moved`` is the INTERFACE-REQUIRED traffic (inputs read once +
    outputs written once), not what the compiled program happens to move —
    so pct_of_hbm_peak is an honest efficiency (achieving 100% requires a
    single fused pass with no spills or re-reads).
    """
    gbps = bytes_moved / seconds / 1e9
    peak = _hbm_peak_gbps()
    return {
        "bytes_min_per_dispatch": int(bytes_moved),
        "effective_gbps": round(gbps, 1),
        "pct_of_hbm_peak": (None if peak is None
                            else round(100.0 * gbps / peak, 1)),
    }


def _pass_accounting(info, res_iters, b, t, fit_s):
    """VERDICT r4 item 2: publish what a fit actually spends.

    ``info`` is the optimizer's ``count_evals`` dict; the returned block
    records full-batch linesearch value passes, value+grad passes, the
    compaction split, and a full-batch-equivalent total (a fused value+grad
    pass streams ~3x the panel bytes of a value-only pass: forward read +
    trajectory store + backward re-read).  ``objective_effective_gbps`` is
    that traffic over the measured fit wall time — a lower bound on the
    device streaming rate since the wall includes one dispatch round trip.
    """
    ca = int(info["compact_at"])
    cap = int(info["cap"])
    ls = np.asarray(info["ls_evals"])
    k_end = int(np.asarray(res_iters).max())
    ls1, ls2 = int(ls[:ca].sum()), int(ls[ca:k_end].sum())
    vg1, vg2 = ca + 1, k_end - ca  # +1: the init value+grad pass
    frac = (cap / b) if cap else 1.0
    equiv = ls1 + 3 * vg1 + frac * (ls2 + 3 * vg2)
    return {
        "objective_passes_per_fit": {
            "outer_iters": k_end,
            "ls_value_passes_full_batch": ls1,
            "value_grad_passes_full_batch": vg1,
            "ls_value_passes_compacted": ls2,
            "value_grad_passes_compacted": vg2,
            "compact_at_iter": ca,
            "compact_cap_rows": cap,
            "full_batch_value_pass_equivalents": round(equiv, 1),
        },
        "objective_effective_gbps_incl_dispatch": round(
            equiv * b * t * 4 / fit_s / 1e9, 1),
    }


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _progress(msg):
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()


# ---------------------------------------------------------------------------
# synthetic data (host-side numpy; device transfer happens before timing)
# ---------------------------------------------------------------------------


def gen_arima_panel(b, t, seed=0, phi=0.6, theta=0.3):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i] + theta * e[:, i - 1]
    return np.cumsum(y, axis=1)  # d=1 integration


def gen_garch_returns(b, t, seed=0, omega=0.05, alpha=0.12, beta=0.8):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(b, t)).astype(np.float32)
    r = np.zeros_like(z)
    h = np.full((b,), omega / (1 - alpha - beta), np.float32)
    rprev = np.zeros((b,), np.float32)
    for i in range(t):
        h = omega + alpha * rprev**2 + beta * h
        r[:, i] = np.sqrt(h) * z[:, i]
        rprev = r[:, i]
    return r


def gen_seasonal_panel(b, t, m, seed=0):
    rng = np.random.default_rng(seed)
    tt = np.arange(t, dtype=np.float32)
    base = 10.0 + 0.02 * tt[None, :]
    phase = rng.uniform(0, 2 * np.pi, (b, 1)).astype(np.float32)
    seas = 2.0 * np.sin(2 * np.pi * tt[None, :] / m + phase)
    return (base + seas + rng.normal(scale=0.3, size=(b, t))).astype(np.float32)


def gen_gappy_panel(b, t, seed=0, gap_frac=0.1):
    rng = np.random.default_rng(seed)
    y = np.cumsum(rng.normal(size=(b, t)), axis=1).astype(np.float32)
    mask = rng.random((b, t)) < gap_frac
    mask[:, 0] = False  # keep edges so linear fill is interior
    mask[:, -1] = False
    y[mask] = np.nan
    return y


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------


def time_calls(run, variants):
    """``run(v) -> host float`` (the host reduction is the sync point).
    First call compiles/warms; returns per-call durations over ``variants``."""
    run(variants[0])
    times = []
    for v in variants:
        t0 = time.perf_counter()
        run(v)
        times.append(time.perf_counter() - t0)
    return times


def stage(jnp, arrs):
    """Move arrays to device and force the transfers to finish."""
    out = [jnp.asarray(a) for a in arrs]
    for o in out:
        float(jnp.sum(jnp.nan_to_num(o[:1])))
    return out


# ---------------------------------------------------------------------------
# CPU oracles (single core; per-series loops like the reference)
# ---------------------------------------------------------------------------


def _rate_loop(one_series, panel, budget_s, chunk: int = 64):
    """Per-series rate: run ``one_series(row)`` until the budget is spent.

    The rate is the FASTEST observed per-chunk rate, not the whole-run
    average: the bench host is shared, and a contended stretch would
    otherwise understate the CPU oracle (and overstate every speedup) by
    2x between runs.  Best-of timing gives the CPU its best case — the
    same convention the device side's min-of-N timing uses.
    """
    t0 = time.perf_counter()
    done = 0
    best_rate = 0.0
    c0, cn = t0, 0
    for row in panel:
        one_series(row)
        done += 1
        cn += 1
        now = time.perf_counter()
        if cn >= chunk:
            best_rate = max(best_rate, cn / (now - c0))
            c0, cn = now, 0
        if now - t0 > budget_s:
            break
    dt = time.perf_counter() - t0
    # fold the partial tail only when it is a meaningful sample: a 1-row
    # "chunk" would let one cheap row (or timer jitter) set the oracle rate
    if cn >= max(chunk // 2, 2):
        best_rate = max(best_rate, cn / (time.perf_counter() - c0))
    return max(best_rate, done / dt), done


@functools.lru_cache(maxsize=8)
def cpu_rate_autocorr(t, num_lags, budget_s):
    rng = np.random.default_rng(1)
    panel = np.cumsum(rng.normal(size=(4096, t)), axis=1)

    def one(x):
        d = x - x.mean()
        denom = float(d @ d)
        return [float(d[k:] @ d[:-k]) / denom for k in range(1, num_lags + 1)]

    return _rate_loop(one, panel, budget_s)


def cpu_rate_fill_chain(t, budget_s):
    panel = gen_gappy_panel(4096, t, seed=2).astype(np.float64)
    idx = np.arange(t)

    def one(x):
        valid = ~np.isnan(x)
        f = np.interp(idx, idx[valid], x[valid])
        d = np.diff(f)
        lagged = np.concatenate([[np.nan], f[:-1]])
        return d, lagged

    return _rate_loop(one, panel, budget_s)


def _css_nll_lfilter(params, y, lfilter):
    """ARIMA(1,0,1)+c CSS objective at C speed (the JVM-loop stand-in)."""
    c, phi, theta = params
    n = y.shape[0]
    u = np.empty_like(y)
    u[0] = 0.0  # conditional: first p errors zeroed
    u[1:] = y[1:] - c - phi * y[:-1]
    e = lfilter([1.0], [1.0, theta], u)
    e[0] = 0.0
    n_eff = n - 1
    css = float(e @ e)
    sigma2 = css / n_eff
    return 0.5 * n_eff * (np.log(2.0 * np.pi * sigma2) + 1.0)


def cpu_rate_arima(t, budget_s):
    from scipy.optimize import minimize
    from scipy.signal import lfilter

    panel = np.diff(gen_arima_panel(512, t, seed=3).astype(np.float64), axis=1)

    def one(yd):
        res = minimize(
            _css_nll_lfilter, np.array([0.0, 0.3, 0.1]), args=(yd, lfilter),
            method="L-BFGS-B", options={"maxiter": 60},
        )
        return res.x

    return _rate_loop(one, panel, budget_s)


def _garch_nll_lfilter(params, r2, lfilter):
    omega, alpha, beta = params
    if omega <= 0 or alpha < 0 or beta < 0 or alpha + beta >= 1:
        return 1e12
    h0 = float(r2.mean())
    drive = omega + alpha * np.concatenate([[h0], r2[:-1]])
    # h_t = drive_t + beta h_{t-1}, h_{-1} = h0
    h = lfilter([1.0], [1.0, -beta], drive)
    h += (beta ** np.arange(1, len(drive) + 1)) * h0
    h = np.maximum(h, 1e-12)
    return 0.5 * float(np.sum(np.log(2 * np.pi * h) + r2 / h))


def cpu_rate_garch(t, budget_s):
    from scipy.optimize import minimize
    from scipy.signal import lfilter

    panel = (gen_garch_returns(512, t, seed=4).astype(np.float64)) ** 2

    def one(r2):
        res = minimize(
            _garch_nll_lfilter, np.array([0.05, 0.1, 0.8]), args=(r2, lfilter),
            method="L-BFGS-B",
            bounds=[(1e-8, None), (0.0, 1.0), (0.0, 1.0)],
            options={"maxiter": 80},
        )
        return res.x

    return _rate_loop(one, panel, budget_s)


def _hw_sse_np(P, Y, m):
    """Batch-vectorized Holt-Winters additive SSE: ``P [B,3]``, ``Y [B,t]``
    -> ``[B]``.  The recursion is serial in t but vectorized across series
    (VERDICT r3 item 5 — the honest CPU bar: one numpy op per step covers
    the whole batch, exactly what a tuned CPU implementation would do)."""
    a, bb, g = P[:, 0].copy(), P[:, 1].copy(), P[:, 2].copy()
    na, nb, ng = 1.0 - a, 1.0 - bb, 1.0 - g
    Yf = np.asfortranarray(Y)  # contiguous column reads inside the t-loop
    level = Y[:, :m].mean(axis=1)
    trend = (Y[:, m : 2 * m].mean(axis=1) - level) / m
    seas = np.ascontiguousarray((Y[:, :m] - level[:, None]).T)  # [m, B]
    sse = np.zeros(Y.shape[0])
    for t in range(Y.shape[1]):
        yt = Yf[:, t]
        s = seas[t % m]
        d = yt - s
        lt = level + trend
        if t >= m:
            r = d - lt
            r *= r
            sse += r
        nl = a * d
        nl += na * lt
        trend *= nb
        trend += bb * (nl - level)
        s *= ng
        s += g * (yt - nl)  # in-place: s aliases the seas[t % m] row
        level = nl
    return sse


def cpu_rate_hw(t, m, budget_s):
    """Holt-Winters CPU oracle: projected gradient descent with batched
    forward-difference gradients on the vectorized SSE — every objective
    evaluation covers the whole batch in one numpy recursion.  The iteration
    budget (60) matches the scipy L-BFGS-B budget the other oracles use."""
    B = 64 if budget_s < 5 else 2048
    panel = gen_seasonal_panel(B, t, m, seed=5).astype(np.float64)
    t0 = time.perf_counter()
    n_evals = 0
    min_eval = float("inf")

    def ev(Pq):
        # the uniform unit of work: one batched SSE evaluation.  Best-of
        # timing happens at THIS granularity (iterations do varying numbers
        # of evals, so a per-iteration min would pick a cheap-work iteration,
        # not an uncontended one)
        nonlocal n_evals, min_eval
        e0 = time.perf_counter()
        out = _hw_sse_np(Pq, panel, m)
        dt = time.perf_counter() - e0
        n_evals += 1
        min_eval = min(min_eval, dt)
        return out

    P = np.tile(np.array([0.3, 0.1, 0.1]), (B, 1))
    f = ev(P)
    step = np.full(B, 0.1)
    eps = 1e-7
    iters_done = 0
    for _ in range(60):
        grad = np.empty((B, 3))
        for k in range(3):
            Pk = P.copy()
            Pk[:, k] += eps
            grad[:, k] = (ev(Pk) - f) / eps
        gn = np.linalg.norm(grad, axis=1) + 1e-30
        accepted = np.zeros(B, bool)
        ts = step.copy()  # per-row trial scale for THIS linesearch
        for _ls in range(4):  # batched backtracking linesearch
            cand = np.clip(P - (ts / gn)[:, None] * grad, 1e-4, 1.0 - 1e-4)
            fc = ev(np.where(accepted[:, None], P, cand))
            better = ~accepted & (fc < f)
            P[better] = cand[better]
            f[better] = fc[better]
            step[better] = ts[better] * 1.2  # grow ONCE, from the accepted scale
            accepted |= better
            ts = np.where(accepted, ts, ts * 0.5)
            if accepted.all():
                break
        # rows that failed every scale resume below the smallest tried one;
        # each row's step depends only on its own accept/reject history
        step[~accepted] = ts[~accepted]
        iters_done += 1
        if time.perf_counter() - t0 > budget_s:
            break
    # best-of timing, the same convention _rate_loop and the device side's
    # min-of-N use, applied per EVALUATION (the uniform work unit): per-fit
    # cost = the evals a full 60-iteration run performs, each charged at the
    # fastest uncontended evaluation time
    evals_per_full_run = n_evals * (60.0 / iters_done)
    rate = B / (evals_per_full_run * min_eval)
    return rate, int(B * iters_done / 60.0)


# ---------------------------------------------------------------------------
# TPU-side configs
# ---------------------------------------------------------------------------


def _speedup_line(name, value, unit, cpu_rate, n_done, extra=None):
    n_cores = os.cpu_count() or 1
    all_core = cpu_rate * n_cores
    speedup = value / all_core if all_core > 0 else float("nan")
    obj = {
        "metric": name,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(speedup / SPEEDUP_TARGET, 4),
        "cpu_series_per_sec_1core": round(cpu_rate, 2),
        "cpu_series_per_sec_allcore_est": round(all_core, 1),
        "cpu_oracle_series_measured": n_done,
        "speedup_vs_cpu_allcore": round(speedup, 2),
    }
    if extra:
        obj.update(extra)
    return obj


def bench_autocorr(jnp, quick):
    import jax

    from spark_timeseries_tpu.ops import univariate as uv

    b, t, lags = (256, 200, 5) if quick else (1024, 1000, 10)
    kern = uv.batch_autocorr(lags)  # jitted internally, both backends
    panels = [
        np.cumsum(np.random.default_rng(s).normal(size=(b, t)), axis=1).astype(np.float32)
        for s in range(4)
    ]
    dev = stage(jnp, panels)
    times = time_calls(lambda v: float(jnp.sum(kern(v))), dev)
    rate = b / min(times)

    # device-time companion: one wall dispatch at this size is mostly
    # host round-trip; difference K-chained kernels in one
    # jitted program against a structurally identical single-kernel program
    # (paired interleaved timing, _marginal) so the fixed round-trip cancels
    # and what remains is per-kernel on-device time
    KD = 33

    def make_chained(k):
        @jax.jit
        def chained(v):
            s = 0.0
            for i in range(k):
                s = s + jnp.sum(kern(v + 0.1 * i))
            return s

        return chained

    chained, chained1 = make_chained(KD), make_chained(1)
    float(chained(dev[0]))  # warm/compile outside the paired timing
    float(chained1(dev[0]))
    device_time, device_rate_ = _marginal(
        lambda: float(chained(dev[0])), lambda: float(chained1(dev[0])),
        KD, b, 3 * b * t * 4)  # real streamed traffic per marginal kernel:
    # the v+0.1*i materialization (write + read) plus the kernel's read —
    # same accounting as config1b's physics clamp
    device_rate = device_rate_

    cpu_rate, n_done = cpu_rate_autocorr(t, lags, 2.0 if quick else CPU_BUDGET_S / 3)
    n_cores = os.cpu_count() or 1
    return _speedup_line(
        f"config1: autocorr({lags}) mapSeries equivalent, {b}x{t} "
        "(BASELINE-fixed size; one small dispatch is round-trip-latency-bound "
        "— device_time_s_est is the on-device kernel time "
        "with the round-trip differenced out; see config1b for the at-scale "
        "rate)",
        rate, "series/sec", cpu_rate, n_done,
        extra={
            "device_time_s_est":
                None if device_time is None else round(device_time, 6),
            "device_series_per_sec":
                None if device_rate is None else round(device_rate, 1),
            "device_speedup_vs_cpu_allcore":
                None if device_rate is None else round(
                    device_rate / max(cpu_rate * n_cores, 1e-9), 2),
        },
    )


def _stage_folded(variant, K):
    """Stage K distinct FOLDED variants on device, all outside any timed
    region (the residency model: a panel is folded once at ingest and then
    lives in kernel layout — ``ops.layout``).  Returns the folded panels and
    the measured one-time fold cost per panel."""
    import jax

    from spark_timeseries_tpu.ops.layout import fold_panel

    fold_jit = jax.jit(fold_panel)  # FoldedPanel is a registered pytree
    folded, fold_times = [], []
    for i in range(K):
        v = variant(i)
        jax.block_until_ready(v)
        t0 = time.perf_counter()
        fp = fold_jit(v)
        jax.block_until_ready(fp.data)
        fold_times.append(time.perf_counter() - t0)
        folded.append(fp)
    # first call pays the fold compile; the per-panel cost is the rest
    once = float(np.median(fold_times[1:])) if K > 1 else fold_times[0]
    return folded, once


def bench_autocorr_at_scale(jnp, quick, on_tpu):
    """Same kernel at panel scale, where dispatch latency amortizes.

    K panels are processed per dispatch (distinct device-resident inputs
    inside ONE jitted program — the steady state of any pipeline that keeps
    the chip fed): a single short kernel call is otherwise buried under
    its own dispatch round-trip.

    PRIMARY methodology (VERDICT r4 item 3): the panels are RESIDENT in the
    folded kernel layout (``ops.layout.fold_panel`` — one transpose at
    ingest, amortized over the panel's lifetime), so the kernel's marginal
    traffic is the interface minimum: one panel read.  The natural-layout
    program (fold inside every dispatch) is kept as companion fields for
    cross-round comparability.
    """
    import jax

    from spark_timeseries_tpu.ops import pallas_kernels as pk
    from spark_timeseries_tpu.ops import univariate as uv

    b, t, lags = (2048, 200, 5) if quick or not on_tpu else (131_072, 1000, 10)
    K = 2 if quick else 8
    kern = uv.batch_autocorr(lags)  # jitted internally, both backends

    def make_many(k):
        @jax.jit
        def many(v):
            s = 0.0
            for i in range(k):
                s = s + jnp.sum(kern(v + 0.1 * i))  # distinct input per call
            return s

        return many

    many, many1 = make_many(K), make_many(1)

    panels = [
        np.cumsum(np.random.default_rng(s).normal(size=(b, t)), axis=1).astype(np.float32)
        for s in range(3)
    ]
    dev = stage(jnp, panels)
    # natural-layout program: the fold (HBM transpose) rides every dispatch
    times_nat = time_calls(lambda v: float(many(v)), dev * 2)
    rate_nat = K * b / min(times_nat)
    # ADVICE r3: also publish the single-dispatch rate so cross-round
    # comparisons can't silently mix amortized and unamortized methodology
    times1 = time_calls(lambda v: float(many1(v)), dev * 2)
    rate1 = b / min(times1)
    per_marg_nat, rate_marg_nat = _marginal(
        lambda: float(many(dev[0])), lambda: float(many1(dev[0])),
        K, b, 3 * b * t * 4)

    # resident folded layout: the primary measurement
    folded_extra = {}
    rate = rate_nat
    times = times_nat
    use_folded = on_tpu and pk.supported(jnp.float32, t)
    if use_folded:
        folded, fold_once = _stage_folded(lambda i: dev[0] + 0.1 * i, K)

        def make_folded(k):
            @jax.jit
            def prog(ps):
                s = 0.0
                for i in range(k):
                    s = s + jnp.sum(kern(ps[i]))
                return s

            return prog

        progK, prog1 = make_folded(K), make_folded(1)
        times = time_calls(lambda _: float(progK(folded)), [0, 1, 2])
        rate = K * b / min(times)
        float(prog1(folded))  # warm the 1-panel program before pairing
        per_marg, rate_marg = _marginal(
            lambda: float(progK(folded)), lambda: float(prog1(folded)),
            K, b, b * t * 4)
        folded_extra = {
            "layout": "folded-resident (ops.layout; fold paid once at ingest)",
            "fold_once_s_per_panel": round(fold_once, 4),
            "per_panel_s_marginal":
                None if per_marg is None else round(per_marg, 5),
            "series_per_sec_marginal":
                None if rate_marg is None else round(rate_marg, 1),
            "roofline_marginal":
                None if per_marg is None else _roofline(b * t * 4, per_marg),
        }

    cpu_rate, n_done = cpu_rate_autocorr(t, lags, 2.0 if quick else CPU_BUDGET_S / 3)
    layout_desc = (
        "resident folded layout; marginal = dispatch-cost-free device "
        "throughput; *_with_fold companions pay the layout transpose inside "
        "every dispatch" if use_folded else
        "natural layout — no TPU, folded path not measured"
    )
    return _speedup_line(
        f"config1b: autocorr({lags}) at scale, {b}x{t} "
        f"({K} panels per dispatch, {layout_desc})",
        rate, "series/sec", cpu_rate, n_done,
        extra={"per_dispatch_s": round(min(times), 4), "panels_per_dispatch": K,
               **folded_extra,
               "series_per_sec_with_fold": round(rate_nat, 1),
               "per_dispatch_s_single_with_fold": round(min(times1), 4),
               "series_per_sec_single_dispatch_with_fold": round(rate1, 1),
               "per_panel_s_marginal_with_fold":
                   None if per_marg_nat is None else round(per_marg_nat, 5),
               "series_per_sec_marginal_with_fold":
                   None if rate_marg_nat is None else round(rate_marg_nat, 1),
               "roofline_marginal_with_fold":
                   None if per_marg_nat is None else _roofline(
                       b * t * 4, per_marg_nat),
               # the with-fold program's real streamed traffic (fold
               # transpose write + read plus the kernel's read)
               "roofline_marginal_actual_moved_with_fold":
                   None if per_marg_nat is None else _roofline(
                       3 * b * t * 4, per_marg_nat),
               **_roofline(K * b * t * 4, min(times))},
    )


def bench_fill_chain(jnp, quick, on_tpu):
    import jax

    from spark_timeseries_tpu.ops import pallas_kernels as pk
    from spark_timeseries_tpu.ops import univariate as uv

    # one dispatch over the whole panel: the fused two-phase Pallas chain
    # (falling back to the gather-free fill scans off-TPU) keeps the
    # 100k x 1k compile tractable, and a single call avoids paying the
    # dispatch round-trip once per chunk
    b = 2048 if quick or not on_tpu else 98_304
    t = 200 if quick else 1000
    K = 2 if quick else 8  # panels per dispatch: amortizes host round-trips
    # the outputs materialize (jit results), one scalar sync per dispatch

    def make_chain(k):
        @jax.jit
        def chain(v):
            s = 0.0
            for i in range(k):
                f, d, lagged = uv.batch_fill_linear_chain(v + 0.25 * i)
                s = s + jnp.sum(jnp.nan_to_num(d)) + jnp.sum(jnp.nan_to_num(lagged))
            return s

        return chain

    chain, chain1 = make_chain(K), make_chain(1)

    def run(v):
        return float(chain(v))

    # ONE host generation + transfer; variants derive on device (the offset
    # propagates NaN gaps unchanged) so min-of-N timing measures the kernel,
    # not transfer jitter (one-dispatch timing had 3.5x spread in round 2)
    base = stage(jnp, [gen_gappy_panel(b, t, seed=2)])[0]
    variants = [base + 0.25 * K * (i + 1) for i in range(3)]
    for v in variants:
        jax.block_until_ready(v)
    times_nat = time_calls(run, variants * 2)
    rate_nat = K * b / min(times_nat)

    # ADVICE r3: single-dispatch companion rate (unamortized methodology;
    # structurally identical program with K=1, so the marginal difference
    # isolates exactly K-1 extra kernel passes)
    times1 = time_calls(lambda v: float(chain1(v)), variants * 2)
    rate1 = b / min(times1)
    per_marg_nat, rate_marg_nat = _marginal(
        lambda: float(chain(variants[0])), lambda: float(chain1(variants[0])),
        K, b, 9 * b * t * 4)

    # PRIMARY methodology (VERDICT r4 items on traffic + output selection):
    # resident folded panels, and only the two outputs the workload (and the
    # CPU oracle) actually consume — the chain's interface minimum is then
    # 1 panel read + 2 writes, and the fused kernel's intermediates never
    # touch HBM
    folded_extra = {}
    rate, times = rate_nat, times_nat
    n_out = 2
    use_folded = on_tpu and pk.supported(jnp.float32, t)
    if use_folded:
        folded, fold_once = _stage_folded(lambda i: base + 0.25 * (i + 1), K)

        def make_folded(k):
            @jax.jit
            def prog(ps):
                s = 0.0
                for i in range(k):
                    d, lagged = pk.fill_linear_chain_folded(ps[i], ("diff", "lag"))
                    s = (s + jnp.sum(jnp.nan_to_num(d.data))
                         + jnp.sum(jnp.nan_to_num(lagged.data)))
                return s

            return prog

        progK, prog1 = make_folded(K), make_folded(1)
        times = time_calls(lambda _: float(progK(folded)), [0, 1, 2])
        rate = K * b / min(times)
        float(prog1(folded))  # warm the 1-panel program before pairing
        per_marg, rate_marg = _marginal(
            lambda: float(progK(folded)), lambda: float(prog1(folded)),
            K, b, (1 + n_out) * b * t * 4)
        folded_extra = {
            "layout": "folded-resident, outputs=('diff','lag') "
                      "(ops.layout; fold paid once at ingest)",
            "fold_once_s_per_panel": round(fold_once, 4),
            "per_panel_s_marginal":
                None if per_marg is None else round(per_marg, 5),
            "series_per_sec_marginal":
                None if rate_marg is None else round(rate_marg, 1),
            "roofline_marginal":
                None if per_marg is None else _roofline(
                    (1 + n_out) * b * t * 4, per_marg),
        }

    cpu_rate, n_done = cpu_rate_fill_chain(t, 2.0 if quick else CPU_BUDGET_S / 3)
    # interface-required traffic for the folded program: read the resident
    # gappy panel once, write the two requested outputs once.  The
    # *_with_fold companions run the natural-layout three-output chain
    # (fold + unfold transposes inside the dispatch, ~9 panel passes) for
    # cross-round comparability
    npass_dispatch = (1 + n_out) if use_folded else 4  # natural: read + 3 outs
    layout_desc = (
        "resident folded layout, 2 requested outputs; marginal = "
        "dispatch-cost-free device throughput" if use_folded else
        "natural layout, 3 outputs — no TPU, folded path not measured"
    )
    return _speedup_line(
        f"config2: fillLinear+difference+lag chain, {b}x{t} "
        f"({K} panels per dispatch, {layout_desc})",
        rate, "series/sec", cpu_rate, n_done,
        extra={"per_dispatch_s": [round(x, 4) for x in times],
               "panels_per_dispatch": K,
               **folded_extra,
               "series_per_sec_with_fold": round(rate_nat, 1),
               "per_dispatch_s_single_with_fold": round(min(times1), 4),
               "series_per_sec_single_dispatch_with_fold": round(rate1, 1),
               "per_panel_s_marginal_with_fold":
                   None if per_marg_nat is None else round(per_marg_nat, 5),
               "series_per_sec_marginal_with_fold":
                   None if rate_marg_nat is None else round(rate_marg_nat, 1),
               "roofline_marginal_with_fold":
                   None if per_marg_nat is None else _roofline(
                       4 * b * t * 4, per_marg_nat),
               "roofline_marginal_actual_moved_with_fold":
                   None if per_marg_nat is None else _roofline(
                       9 * b * t * 4, per_marg_nat),
               **_roofline(K * npass_dispatch * b * t * 4, min(times))},
    )


def bench_garch(jnp, quick, on_tpu):
    from spark_timeseries_tpu.models import garch

    b = 1024 if quick or not on_tpu else 50_000
    t = 200 if quick else 1000
    panels = [gen_garch_returns(b, t, seed=s) for s in range(3)]
    dev = stage(jnp, panels)

    conv = {}

    def run(v):
        r = garch.fit(v)
        conv["frac"] = float(jnp.mean(r.converged))
        return float(jnp.sum(jnp.nan_to_num(r.params)))

    times = time_calls(run, dev)
    rate = b / min(times)
    # pass accounting (VERDICT r4 item 2): one instrumented fit
    acct = {}
    if on_tpu:
        r_i, info = garch.fit(dev[0], count_evals=True)
        acct = _pass_accounting(info, r_i.iters, b, t, min(times))
    cpu_rate, n_done = cpu_rate_garch(t, 2.0 if quick else CPU_BUDGET_S)
    return _speedup_line(
        f"config4: GARCH(1,1) fit, {b} tickers x {t} obs, converged {conv['frac']:.2f}",
        rate, "series/sec", cpu_rate, n_done,
        extra={"converged_frac": round(conv["frac"], 4), **acct},
    )


def bench_holtwinters(jnp, quick, on_tpu):
    import jax

    from spark_timeseries_tpu.models import holtwinters as hw

    m = 24
    if quick or not on_tpu:
        chunk, n_chunks, t = 1024, 1, 96
    else:
        chunk, n_chunks, t = 131_072, 8, 960  # 1,048,576 series total
    total = chunk * n_chunks

    conv = []

    def fit_chunk(v):
        r = hw.fit(v, m, "additive", max_iters=40)
        conv.append(float(jnp.mean(r.converged)))
        return float(jnp.sum(jnp.nan_to_num(r.params)))

    # ONE host generation + transfer; per-chunk variants derive on device
    # with a fresh random field each (a scalar offset would leave every
    # chunk's convergence behavior identical — ADVICE round 2 — while
    # host-side generation would ship ~4 GB host-to-device)
    base = stage(jnp, [gen_seasonal_panel(chunk, t, m, seed=0)])[0]

    def variant(i):
        noise = 0.15 * jax.random.normal(jax.random.key(i), base.shape, base.dtype)
        return base + noise + 0.01 * i

    fit_chunk(variant(1000))  # warm/compile
    conv.clear()

    elapsed = 0.0
    for i in range(n_chunks):
        v = variant(i)
        jax.block_until_ready(v)  # materialize the variant outside the timing
        t0 = time.perf_counter()
        fit_chunk(v)
        elapsed += time.perf_counter() - t0
        del v
    rate = total / elapsed
    frac = float(np.mean(conv))
    # pass accounting (VERDICT r4 item 2): one instrumented chunk fit
    acct = {}
    if on_tpu:
        v = variant(0)
        jax.block_until_ready(v)
        r_i, info = hw.fit(v, m, "additive", max_iters=40, count_evals=True)
        acct = _pass_accounting(info, r_i.iters, chunk, t, elapsed / n_chunks)
    cpu_rate, n_done = cpu_rate_hw(t, m, 2.0 if quick else CPU_BUDGET_S)
    return _speedup_line(
        f"config5: HoltWinters additive (period {m}) fit, {total} hourly series x "
        f"{t} obs, converged {frac:.2f} (CPU oracle: batch-vectorized numpy "
        "recursion + FD gradient descent, 60-iteration budget)",
        rate, "series/sec", cpu_rate, n_done,
        extra={"converged_frac": round(frac, 4), "chunks": n_chunks, **acct},
    )


def check_backend_parity(jnp, on_tpu):
    """Native-lowering guard: the fused Pallas objectives must agree with the
    portable scan objectives ON DEVICE before any timing (ADVICE round 1)."""
    if not on_tpu:
        return {"checked": False, "reason": "no TPU; scan backend is the oracle"}
    from spark_timeseries_tpu.models import arima, ewma, garch
    from spark_timeseries_tpu.models import holtwinters as hw

    # the gate must hold under `python -O` too, so no bare asserts here
    def _gate(ok, msg):
        if not ok:
            raise RuntimeError(msg)

    def _both_conv_maxdiff(name, a, b):
        # the diff is meaningful only over rows BOTH backends converged, and
        # only if that overlap is substantial — an empty overlap must FAIL,
        # not pass vacuously (a kernel that never converges diffs as 0.0)
        both = a.converged & b.converged
        frac = float(jnp.mean(both.astype(jnp.float32)))
        _gate(frac > 0.8,
              f"{name}: only {frac:.2f} of rows converged on both backends")
        return float(
            jnp.max(jnp.where(both[:, None], jnp.abs(a.params - b.params), 0.0))
        )

    y = jnp.asarray(gen_arima_panel(1024, 200, seed=7))
    rs = arima.fit(y, (1, 1, 1), backend="scan", max_iters=30)
    rp = arima.fit(y, (1, 1, 1), backend="pallas", max_iters=30)
    da = _both_conv_maxdiff("ARIMA", rs, rp)
    # forecast rides the native "tail" kernel mode (css_last_errors) in the
    # headline config: gate its NATIVE lowering against the scan rebuild
    # (non-invertible MA rows blow up identically in both; gate finite rows
    # and require the non-finite masks to agree)
    fc_s = np.asarray(arima.forecast(rs.params, y, (1, 1, 1), 10, backend="scan"))
    fc_p = np.asarray(arima.forecast(rs.params, y, (1, 1, 1), 10, backend="pallas"))
    fin = np.isfinite(fc_s).all(axis=1)
    _gate(fin.mean() > 0.8, f"ARIMA forecast: only {fin.mean():.2f} finite rows")
    _gate(bool((np.isfinite(fc_s) == np.isfinite(fc_p)).all()),
          "ARIMA forecast scan/pallas non-finite masks disagree")
    dfc = float(np.abs(fc_s[fin] - fc_p[fin]).max()) if fin.any() else 0.0
    _gate(dfc < 1e-2, f"ARIMA forecast pallas/scan divergence on device: {dfc}")
    r = jnp.asarray(gen_garch_returns(1024, 200, seed=8))
    gs = garch.fit(r, backend="scan", max_iters=40)
    gp = garch.fit(r, backend="pallas", max_iters=40)
    # the GARCH likelihood is non-convex: a handful of rows can legitimately
    # converge to DIFFERENT local optima per backend (observed ~0.2%), so —
    # exactly like Holt-Winters below — gate the achieved-objective
    # distribution and the typical parameter agreement, not the max
    g_both = np.asarray(gs.converged & gp.converged)
    _gate(g_both.mean() > 0.8,
          f"GARCH: only {g_both.mean():.2f} of rows converged on both backends")
    g_rel = np.asarray(jnp.abs(
        (gs.neg_log_likelihood - gp.neg_log_likelihood)
        / jnp.maximum(jnp.abs(gs.neg_log_likelihood), 1e-6)
    ))[g_both]
    dg = float(np.percentile(g_rel, 99)) if g_rel.size else 0.0
    dg_frac_big = float((g_rel > 0.05).mean()) if g_rel.size else 0.0
    dg_med = float(jnp.nanmedian(jnp.abs(gs.params - gp.params)))
    dg_conv = abs(float(jnp.mean(gs.converged)) - float(jnp.mean(gp.converged)))
    x = jnp.asarray(np.cumsum(
        np.random.default_rng(9).normal(size=(1024, 200)).astype(np.float32), axis=1
    ))
    es = ewma.fit(x, backend="scan")
    ep = ewma.fit(x, backend="pallas")
    de = _both_conv_maxdiff("EWMA", es, ep)
    w = jnp.asarray(gen_seasonal_panel(1024, 192, 24, seed=10))
    hs = hw.fit(w, 24, "additive", backend="scan", max_iters=30)
    hp = hw.fit(w, 24, "additive", backend="pallas", max_iters=30)
    # Holt-Winters beta is weakly identified when alpha ~ 0 (flat SSE
    # valley), so optimizer paths legitimately diverge in parameter space;
    # the backends must agree on the achieved OBJECTIVE over the rows BOTH
    # report converged (a frozen failed-linesearch row says nothing about
    # kernel parity, and it is flagged converged=False)
    both = np.asarray(hs.converged & hp.converged)
    rel = np.asarray(jnp.abs(
        (hs.neg_log_likelihood - hp.neg_log_likelihood)
        / jnp.maximum(jnp.abs(hs.neg_log_likelihood), 1e-6)
    ))[both]
    # a handful of rows can legitimately land in DIFFERENT local minima of
    # the non-convex SSE (observed ~0.1%); gate the distribution, not the max
    dh = float(np.percentile(rel, 99)) if rel.size else 0.0
    dh_frac_big = float((rel > 0.05).mean()) if rel.size else 0.0
    dh_conv = abs(float(jnp.mean(hs.converged)) - float(jnp.mean(hp.converged)))
    dh_med = float(jnp.nanmedian(jnp.abs(hs.params - hp.params)))
    # transform kernels (no fit in the loop): exact parity expected
    from spark_timeseries_tpu.ops import univariate as uv

    g = jnp.asarray(gen_gappy_panel(1024, 200, seed=11))
    f_ref, d_ref, l_ref = uv.batch_fill_linear_chain(g, backend="scan")
    f_pal, d_pal, l_pal = uv.batch_fill_linear_chain(g)
    dfill = float(jnp.max(jnp.where(jnp.isnan(f_ref) | jnp.isnan(f_pal),
                                    0.0, jnp.abs(f_ref - f_pal))))
    dfill = max(dfill, float(jnp.max(jnp.abs(jnp.nan_to_num(d_ref - d_pal)))))
    dfill = max(dfill, float(jnp.max(jnp.abs(jnp.nan_to_num(l_ref - l_pal)))))
    dfill_nan = float(jnp.sum(jnp.isnan(f_ref) != jnp.isnan(f_pal)))
    dfill_nan += float(jnp.sum(jnp.isnan(d_ref) != jnp.isnan(d_pal)))
    dfill_nan += float(jnp.sum(jnp.isnan(l_ref) != jnp.isnan(l_pal)))
    ac_ref = uv.batch_autocorr(10, backend="scan")(g)
    ac_pal = uv.batch_autocorr(10)(g)
    dac = float(jnp.max(jnp.abs(jnp.nan_to_num(ac_ref - ac_pal))))
    _gate(dfill < 1e-4, f"fill_linear pallas/scan divergence on device: {dfill}")
    _gate(dfill_nan == 0, f"fill_linear pallas/scan NaN-mask mismatch: {dfill_nan}")
    _gate(dac < 1e-3, f"batch_autocorr pallas/scan divergence on device: {dac}")
    _gate(da < 5e-2, f"ARIMA pallas/scan divergence on device: {da}")
    _gate(dg < 1e-2, f"GARCH pallas/scan p99 objective divergence: {dg}")
    _gate(dg_frac_big < 5e-3, f"GARCH rows with >5% objective gap: {dg_frac_big}")
    _gate(dg_med < 1e-2, f"GARCH pallas/scan median param divergence: {dg_med}")
    _gate(dg_conv < 0.05, f"GARCH pallas/scan converged-fraction gap: {dg_conv}")
    _gate(de < 1e-2, f"EWMA pallas/scan divergence on device: {de}")
    _gate(dh < 1e-2, f"HoltWinters pallas/scan p99 objective divergence: {dh}")
    _gate(dh_frac_big < 5e-3, f"HoltWinters rows with >5% objective gap: {dh_frac_big}")
    _gate(dh_conv < 0.05, f"HoltWinters pallas/scan converged-fraction gap: {dh_conv}")
    _gate(dh_med < 1e-2, f"HoltWinters pallas/scan median param divergence: {dh_med}")

    # --- multiplicative Holt-Winters + ragged panels, NATIVE lowering
    # (VERDICT r3 item 3: these paths were interpret-verified only; round 1
    # proved the native Mosaic lowering can silently diverge from interpret)
    def _dist_gate(name, a, b, conv_floor=0.8):
        both = np.asarray(a.converged & b.converged)
        _gate(both.mean() > conv_floor,
              f"{name}: only {both.mean():.2f} of rows converged on both backends")
        rel = np.asarray(jnp.abs(
            (a.neg_log_likelihood - b.neg_log_likelihood)
            / jnp.maximum(jnp.abs(a.neg_log_likelihood), 1e-6)
        ))[both]
        p99 = float(np.percentile(rel, 99)) if rel.size else 0.0
        frac_big = float((rel > 0.05).mean()) if rel.size else 0.0
        med = float(jnp.nanmedian(jnp.abs(a.params - b.params)))
        _gate(p99 < 1e-2, f"{name} p99 objective divergence: {p99}")
        _gate(frac_big < 5e-3, f"{name} rows with >5% objective gap: {frac_big}")
        _gate(med < 1e-2, f"{name} median param divergence: {med}")
        return {"obj_p99_rel_diff": p99, "frac_rows_gt5pct": frac_big,
                "param_median_abs_diff": med}

    def _raggedize(arr, seed):
        a = np.array(arr)
        rng = np.random.default_rng(seed)
        cut = rng.integers(0, a.shape[1] // 3, size=a.shape[0])
        a[np.arange(a.shape[1])[None, :] < cut[:, None]] = np.nan
        return jnp.asarray(a)

    wm = jnp.asarray(gen_seasonal_panel(1024, 192, 24, seed=12) + 25.0)
    hm_s = hw.fit(wm, 24, "multiplicative", backend="scan", max_iters=30)
    hm_p = hw.fit(wm, 24, "multiplicative", backend="pallas", max_iters=30)
    mult_gate = _dist_gate("HoltWinters-multiplicative", hm_s, hm_p)

    yr = _raggedize(gen_arima_panel(1024, 200, seed=13), 13)
    ar_s = arima.fit(yr, (1, 1, 1), backend="scan", max_iters=30)
    ar_p = arima.fit(yr, (1, 1, 1), backend="pallas", max_iters=30)
    da_r = _both_conv_maxdiff("ARIMA-ragged", ar_s, ar_p)
    _gate(da_r < 5e-2, f"ARIMA ragged pallas/scan divergence on device: {da_r}")
    rr = _raggedize(gen_garch_returns(1024, 200, seed=14), 14)
    gr_s = garch.fit(rr, backend="scan", max_iters=40)
    gr_p = garch.fit(rr, backend="pallas", max_iters=40)
    garch_ragged_gate = _dist_gate("GARCH-ragged", gr_s, gr_p)
    xr = _raggedize(np.cumsum(
        np.random.default_rng(15).normal(size=(1024, 200)).astype(np.float32),
        axis=1), 15)
    er_s = ewma.fit(xr, backend="scan")
    er_p = ewma.fit(xr, backend="pallas")
    de_r = _both_conv_maxdiff("EWMA-ragged", er_s, er_p)
    _gate(de_r < 1e-2, f"EWMA ragged pallas/scan divergence on device: {de_r}")
    wr = _raggedize(gen_seasonal_panel(1024, 192, 24, seed=16), 16)
    hr_s = hw.fit(wr, 24, "additive", backend="scan", max_iters=30)
    hr_p = hw.fit(wr, 24, "additive", backend="pallas", max_iters=30)
    hw_ragged_gate = _dist_gate("HoltWinters-ragged", hr_s, hr_p)

    # --- sample -> fit recovery (VERDICT r3 item 8): agreement gates pass a
    # kernel that biases both backends identically; generating from KNOWN
    # parameters and requiring both backends to recover them makes the gate
    # bias-sensitive (upstream's sample-then-fit property-test strategy)
    import jax as _jax

    from spark_timeseries_tpu.models import garch as _g

    g_true = np.array([0.10, 0.15, 0.75], np.float32)  # omega, alpha, beta
    keys = _jax.random.split(_jax.random.key(17), 1024)
    rg = _jax.vmap(lambda k: _g.sample(jnp.asarray(g_true), k, 512))(keys)
    rec = {}
    for bk in ("scan", "pallas"):
        rf = garch.fit(rg, backend=bk, max_iters=60)
        med = np.nanmedian(np.asarray(rf.params), axis=0)
        dev = np.abs(med - g_true)
        rec[f"garch_{bk}_median_param_dev"] = [round(float(x), 4) for x in dev]
        # finite-sample spread of the median at B=1024, t=512 is ~0.01;
        # 0.06/0.08 is ~5x margin yet still catches a systematic bias of
        # half a parameter's typical magnitude
        _gate(bool((dev < np.array([0.06, 0.06, 0.08])).all()),
              f"GARCH {bk} sample->fit recovery off: median {med} vs {g_true}")

    # HW innovations-form generator (the model's own data-generating process).
    # The first two seasons are noise-FREE: the model seeds level/trend/
    # seasonal from those observations, and noisy seeds make the optimizer
    # legitimately prefer inflated alpha/gamma (fast recovery from a wrong
    # seed state) — an estimator property that would mask kernel bias here.
    hw_true = np.array([0.4, 0.2, 0.3], np.float64)
    rng = np.random.default_rng(18)
    Bh, Th, mh = 1024, 480, 24
    lvl = np.full((Bh,), 10.0)
    trd = np.full((Bh,), 0.02)
    ring = np.tile(2.0 * np.sin(2 * np.pi * np.arange(mh) / mh), (Bh, 1))
    ys = np.empty((Bh, Th))
    al, be, ga = hw_true
    for tt in range(Th):
        s = ring[:, tt % mh]
        sig = 0.0 if tt < 2 * mh else 0.3
        ys[:, tt] = lvl + trd + s + sig * rng.normal(size=Bh)
        nl = al * (ys[:, tt] - s) + (1 - al) * (lvl + trd)
        trd = be * (nl - lvl) + (1 - be) * trd
        ring[:, tt % mh] = ga * (ys[:, tt] - nl) + (1 - ga) * s
        lvl = nl
    yh = jnp.asarray(ys.astype(np.float32))
    for bk in ("scan", "pallas"):
        hf = hw.fit(yh, mh, "additive", backend=bk, max_iters=40)
        med = np.nanmedian(np.asarray(hf.params), axis=0)
        dev = np.abs(med - hw_true)
        rec[f"hw_{bk}_median_param_dev"] = [round(float(x), 4) for x in dev]
        # measured finite-sample bias of the median at this size is
        # ~(0.09, 0.09, 0.04); ~1.7x margin still trips on any systematic
        # kernel bias of half a parameter's magnitude
        _gate(bool((dev < np.array([0.15, 0.15, 0.10])).all()),
              f"HoltWinters {bk} sample->fit recovery off: median {med} vs {hw_true}")

    return {"checked": True, "arima_max_abs_diff": da,
            "arima_ragged_max_abs_diff": da_r,
            "ewma_ragged_max_abs_diff": de_r,
            "hw_multiplicative": mult_gate,
            "hw_ragged": hw_ragged_gate,
            "garch_ragged": garch_ragged_gate,
            "recovery": rec,
            "garch_obj_p99_rel_diff": dg,
            "garch_frac_rows_gt5pct": dg_frac_big,
            "garch_param_median_abs_diff": dg_med,
            "garch_converged_frac_gap": dg_conv,
            "fill_chain_max_abs_diff": dfill, "autocorr_max_abs_diff": dac,
            "ewma_max_abs_diff": de, "hw_obj_p99_rel_diff": dh,
            "hw_frac_rows_gt5pct": dh_frac_big,
            "hw_converged_frac_gap": dh_conv,
            "hw_param_median_abs_diff": dh_med}


def _arima_panel_on_device(jnp, t, chunk_rows, *, phi=0.6, theta=0.3):
    """On-device integrated-ARMA panel builder shared by the north-star
    walks: returns ``(gen_chunk, assemble)``.

    ``gen_chunk(key)`` generates one ``[chunk_rows, t]`` chunk of the
    exact ARIMA(1,1,1)-process panel; ``assemble(n_chunks)`` places
    chunks ``key(0..n-1)`` into one resident panel by DONATED in-place
    placement — a plain ``jnp.concatenate`` would transiently hold the
    parts AND the output (double HBM), and a generation-time
    RESOURCE_EXHAUSTED sits outside the chunk driver's backoff
    protection.
    """
    from functools import partial as _partial

    import jax

    @jax.jit
    def gen_chunk(key):
        e = jax.random.normal(key, (chunk_rows, t), jnp.float32)

        def step(carry, e_t):
            y_prev, e_prev = carry
            y_t = phi * y_prev + e_t + theta * e_prev
            return (y_t, e_t), y_t

        _, y = jax.lax.scan(step, (e[:, 0], e[:, 0]), e[:, 1:].T)
        y = jnp.concatenate([e[:, :1], y.T], axis=1)
        return jnp.cumsum(y, axis=1)  # d=1 integration

    @_partial(jax.jit, donate_argnums=(0,))
    def place(panel, chunk, row0):
        return jax.lax.dynamic_update_slice(panel, chunk, (row0, 0))

    def assemble(n_chunks):
        panel = jnp.zeros((chunk_rows * n_chunks, t), jnp.float32)
        for i in range(n_chunks):
            v = gen_chunk(jax.random.key(i))
            panel = place(panel, v, jnp.int32(i * chunk_rows))
            del v
        return panel

    return gen_chunk, assemble


def _northstar_1m(jnp, order):
    """The literal BASELINE north-star workload, executed (VERDICT r4 item
    1): ARIMA(1,1,1) fit over 1,048,576 series x 1k obs, one sustained run
    on the chip — now as a JOURNALED-vs-UNJOURNALED pair through ONE
    pipelined ``fit_chunked`` walk (ISSUE 4).  The panel is GENERATED ON
    DEVICE from the exact ARIMA(1,1,1) process (a 4 GB host panel would
    measure generation and the host-to-device copy, not the fit);
    both runs walk it in 131,072-row chunks, compile excluded by a warmup
    fit on the first chunk's shape.

    The pair is the tentpole's acceptance measurement: the UNJOURNALED
    walk is the durability-free ceiling; the JOURNALED walk pays the
    write-ahead commit of every chunk, but on a bounded background
    committer whose fetch + shard + manifest I/O hides under the next
    chunk's device compute.  The artifact reports both walls, the
    journaled/unjournaled ratio, and the driver's measured overlap
    efficiency (fraction of commit wall the driver never waited for —
    the acceptance bar is >= 0.8 with the journaled wall within 5%).
    """
    import jax

    from spark_timeseries_tpu.models import arima

    chunk_b, n_chunks, t = 131_072, 8, 1000
    gen_chunk, assemble = _arima_panel_on_device(jnp, t, chunk_b)

    def sync(x):
        return float(jnp.sum(jnp.nan_to_num(jnp.ravel(x)[:4])))

    warm = gen_chunk(jax.random.key(1000))
    sync(warm)
    r = arima.fit(warm, order)  # compile the 131k-shape fit program
    sync(r.params)
    del warm, r

    # ONE resident [1M, 1k] panel (4 GB f32; see _arima_panel_on_device
    # for the donated-placement rationale).  The per-chunk align-mode NaN
    # probe rides INSIDE the wall (each walk slice is a fresh buffer):
    # one fused reduction + host sync per chunk, the honest serving-path
    # cost of a sliced walk.
    panel = assemble(n_chunks)
    sync(panel)

    import tempfile

    from spark_timeseries_tpu import obs as _obs
    from spark_timeseries_tpu import reliability as _rel
    from spark_timeseries_tpu.obs.memory import peak_memory as _peak_mem

    ckpt_root = os.environ.get("STSTPU_NORTHSTAR_CKPT") or tempfile.mkdtemp(
        prefix="northstar_journal_")

    _pm = _peak_mem()  # before the run: warmup/compile already resident
    peak, peak_src = _pm.bytes, _pm.source

    def _run(checkpoint_dir):
        t0 = time.perf_counter()
        r = _rel.fit_chunked(arima.fit, panel, chunk_rows=chunk_b,
                             resilient=False, order=order,
                             checkpoint_dir=checkpoint_dir)
        return r, time.perf_counter() - t0

    # durability-free ceiling first (its walk order also matches the
    # journaled run, so the pair shares every compiled program)
    r_plain, wall_plain = _run(None)
    _pm = _peak_mem()
    if _pm.bytes and _pm.bytes > (peak or 0):
        peak, peak_src = _pm.bytes, _pm.source

    # journaled + pipelined walk (ISSUE 4): the write-ahead commit of every
    # chunk — host fetch, npz shard, fsync, atomic manifest — runs on the
    # background committer while the device computes the next chunk.
    # Telemetry rides along (enabled here if the env did not already) so
    # the artifact carries the compile/execute split and commit-latency
    # histogram the regression gate diffs against the previous local run.
    # A re-run with the same STSTPU_NORTHSTAR_CKPT resumes from the
    # committed shards (chunks_resumed > 0; the wall is then not a
    # sustained measurement and the rate reports None).
    obs_was_on = _obs.enabled()
    if not obs_was_on:
        _obs.enable()
    try:
        # ISSUE 5 acceptance: the sliced walk must pay ZERO per-chunk
        # align-probe host syncs — the static plan probes the panel at
        # most once per walk (and not at all here: the unjournaled walk
        # above already warmed the per-array-identity cache), counted by
        # models.base.align_mode_on_host via obs
        a0 = (_obs.snapshot() or {}).get("counters", {})
        r_j, wall_j = _run(ckpt_root)
        a1 = (_obs.snapshot() or {}).get("counters", {})
        align_probes = (a1.get("align.host_probes", 0)
                        - a0.get("align.host_probes", 0))
        tele = r_j.meta.get("telemetry")
        # map_series kernel-cache canary (regression-gate input): three
        # fresh-but-identical lambdas must share ONE compiled kernel (the
        # cache keys on bytecode, not object identity — panel._cached
        # _batched), giving a steady 2/3 hit rate.  A keying regression
        # drops it to 0 and the gate flags the drift — this is the only
        # bench path that exercises map_series, so the canary IS the
        # measurement, not a synthetic stand-in.
        from spark_timeseries_tpu import index as _dtix
        from spark_timeseries_tpu.panel import TimeSeriesPanel as _Panel

        c0 = (_obs.snapshot() or {}).get("counters", {})
        tiny = _Panel(
            _dtix.uniform("2024-01-01", periods=32,
                          frequency=_dtix.DayFrequency(1)),
            [f"c{i}" for i in range(4)],
            jnp.ones((4, 32), jnp.float32))
        for _ in range(3):
            tiny.map_series(lambda v: v * 2.0 + 1.0)
        c1 = (_obs.snapshot() or {}).get("counters", {})
        _d = lambda k: c1.get(k, 0) - c0.get(k, 0)
        ms_hits = _d("panel.map_series.cache_hits")
        ms_misses = _d("panel.map_series.cache_misses")
    finally:
        if not obs_was_on:
            _obs.disable()
    _pm = _peak_mem()
    if _pm.bytes and _pm.bytes > (peak or 0):
        peak, peak_src = _pm.bytes, _pm.source

    j = r_j.meta.get("journal", {})
    resumed = bool(j.get("chunks_resumed", 0))
    pipe = r_j.meta.get("pipeline") or {}
    total = chunk_b * n_chunks
    total_conv = float(np.sum(r_j.converged))
    # the pipelined journaled walk must not change a byte of the result —
    # NaN-tolerant per field (excluded/ineligible rows carry NaN params by
    # design, and NaN != NaN under plain array_equal would false-alarm)
    def _field_eq(f):
        a = np.asarray(getattr(r_j, f))
        b = np.asarray(getattr(r_plain, f))
        return np.array_equal(a, b, equal_nan=a.dtype.kind == "f")

    bitwise_ok = all(_field_eq(f) for f in (
        "params", "neg_log_likelihood", "converged", "iters", "status"))

    status_totals = dict(r_j.meta["status_counts"])
    out = {
        "series_total": total,
        "obs_per_series": t,
        "chunks": n_chunks,
        # journaled wall is the headline (the durable serving path);
        # unjournaled is the ceiling the overlap is measured against
        "wall_s": round(wall_j, 3),
        "wall_s_unjournaled": round(wall_plain, 3),
        "journaled_over_unjournaled": (round(wall_j / wall_plain, 4)
                                       if wall_plain > 0 else None),
        "converged_frac": round(total_conv / total, 4),
        "sustained_converged_series_per_sec":
            round(total_conv / wall_j, 1) if (wall_j > 0 and not resumed)
            else None,
        "unjournaled_converged_series_per_sec":
            round(float(np.sum(r_plain.converged)) / wall_plain, 1)
            if wall_plain > 0 else None,
        # ISSUE 4 acceptance: fraction of commit wall time hidden under
        # device compute, as measured by the committer itself
        "overlap_efficiency": pipe.get("overlap_efficiency"),
        "commit_wall_s": pipe.get("commit_wall_s"),
        "hidden_commit_s": pipe.get("hidden_commit_s"),
        "pipeline_depth": pipe.get("depth"),
        # ISSUE 5 acceptance: the input side of the pipeline — fraction of
        # slice-staging wall hidden under compute, the align plan the walk
        # ran under, and the host-sync probe count during the journaled
        # walk (must be <= 1: the static plan probes at most once, never
        # per chunk)
        "input_overlap_efficiency": pipe.get("input_overlap_efficiency"),
        "staging_wall_s": pipe.get("staging_wall_s"),
        "hidden_staging_s": pipe.get("hidden_staging_s"),
        "prefetch_depth": pipe.get("prefetch_depth"),
        "end_to_end_overlap_efficiency":
            pipe.get("end_to_end_overlap_efficiency"),
        "align_mode": r_j.meta.get("align_mode"),
        "align_probes_journaled_walk": align_probes,
        "zero_per_chunk_align_syncs": align_probes <= 1,
        "journaled_bitwise_identical": bitwise_ok,
        "peak_hbm_bytes": peak,
        # which probe produced the reading: "device" = real HBM stats,
        # "host_rss" = process peak RSS fallback (CPU runs — never null)
        "peak_mem_source": peak_src,
        "fit_status_counts": status_totals,
        "oom_backoffs": r_j.meta["oom_backoffs"],
        "chunk_rows_final": r_j.meta["chunk_rows_final"],
        "degraded_by_oom_backoff": bool(r_j.meta["oom_backoffs"]),
        "journal": {
            "dir": ckpt_root,
            "chunks_committed": j.get("chunks_committed", 0),
            "chunks_resumed": j.get("chunks_resumed", 0),
            "run_ids": [j.get("run_id")],
        },
        "data": "generated on device from the exact ARIMA(1,1,1) process "
                "(phi 0.6, theta 0.3, d=1); ONE pipelined journaled walk "
                "(write-ahead shards on the background committer, commit "
                "inside the timed wall) vs the unjournaled ceiling",
    }
    # regression-gate inputs (ROADMAP satellite): the numbers the
    # throughput headline hides, diffed against the previous local run
    if tele:
        chunks_t = tele.get("chunks") or []
        walls = [c.get("wall_s", 0.0) for c in chunks_t if c.get("wall_s")]
        cwalls = [c.get("wall_s", 0.0) for c in chunks_t
                  if c.get("wall_s") and c.get("phase") == "compile+execute"]
        hist = (tele.get("histograms") or {}).get("journal.commit_s") or {}
        out["telemetry_gate_inputs"] = {
            "compile_time_share": (round(sum(cwalls) / sum(walls), 4)
                                   if walls and sum(walls) > 0 else None),
            "journal_commit_s_mean": hist.get("mean"),
            # from the canary above: expected steady state 2/3
            "map_series_cache_hit_rate": (
                round(ms_hits / (ms_hits + ms_misses), 4)
                if (ms_hits + ms_misses) else None),
            "overlap_efficiency": pipe.get("overlap_efficiency"),
            "input_overlap_efficiency": pipe.get("input_overlap_efficiency"),
        }
    return out


def _sharded_northstar(jnp, order, quick, on_tpu):
    """ISSUE 6 acceptance: the paper's target as ONE mesh-wide durable job.

    The SAME panel is walked twice through ``fit_chunked``, both journaled:
    once on a single device (every other PR's serving path) and once
    sharded over the series-axis mesh (one prefetch -> compute -> commit
    lane per device, per-shard journal namespaces, shard 0 merging the ONE
    job manifest).  Reported: the speedup (the number this PR exists for),
    per-shard overlap efficiency (from the merged manifest's telemetry —
    a straggler lane is a journaled fact), and
    ``sharded_bitwise_identical`` — sharding must not change a byte.

    DEGRADED mode (ISSUE 11): a third walk of the same panel with lane 1
    killed mid-job (permanent — its retries fail, the elastic supervisor
    quarantines it and rebalances its chunks onto the survivors).
    Reported: ``degraded_speedup`` (vs the single device — the bar is
    > 1x: losing a lane degrades the mesh win, never erases it),
    ``rebalance_overhead`` (degraded wall over healthy sharded wall − 1),
    and ``degraded_bitwise_identical`` — both wired into the directional
    telemetry regression gate, with an absolute ``degraded_speedup_floor``
    at 1.0.

    On TPU full runs this is the literal 1M x 1k north-star spread over
    all chips; elsewhere a small AR panel proves the scaling on however
    many local (or forced virtual CPU) devices exist.  Every lane device
    is warmed with one chunk-shaped fit first, so neither timed walk pays
    trace/compile and the pair measures execution scaling.
    """
    import tempfile

    import jax

    from spark_timeseries_tpu import obs as _obs
    from spark_timeseries_tpu import reliability as _rel
    from spark_timeseries_tpu.models import arima
    from spark_timeseries_tpu.parallel import mesh as meshlib

    mesh = meshlib.default_mesh()
    lane_devs = meshlib.series_devices(mesh)
    n_lanes = len(lane_devs)
    if n_lanes < 2:
        return {"skipped": True,
                "reason": f"needs >=2 series-axis devices, have {n_lanes}"}

    if on_tpu and not quick:
        # the paper's panel, two chunks per lane: every lane has a NEXT
        # chunk to hide its commits/staging under
        total, t = 1_048_576, 1000
        chunks_per_lane = 2
        chunk_rows = max(1, total // (n_lanes * chunks_per_lane))
    else:
        # CPU sizing is deliberate: virtual devices share the host's
        # cores, so lanes only win by reclaiming the intra-op parallelism
        # XLA leaves idle at small batch — 512-row chunks measure ~2x
        # lane speedup on 2 cores where 8k-row chunks measure ~1x — and
        # the walk needs enough chunks that per-chunk compute dominates
        # the driver's per-chunk bookkeeping and the fixed
        # lane/merge/journal setup (~0.2 s)
        chunk_rows, t = 512, 200
        chunks_per_lane = 25
    total = chunk_rows * n_lanes * chunks_per_lane

    if on_tpu and not quick:
        # generated on device chunk-by-chunk, same process/assembly as
        # _northstar_1m (a 4 GB host panel would measure the H2D copy)
        _gen, assemble = _arima_panel_on_device(jnp, t, chunk_rows)
        panel = assemble(total // chunk_rows)
        panel.block_until_ready()
        warm_host = np.asarray(panel[:chunk_rows])
    else:
        panel = jnp.asarray(gen_arima_panel(total, t, seed=7))
        warm_host = np.asarray(panel[:chunk_rows])

    # warm the walk's EXACT program for BOTH placements: executables are
    # cached per (program, sharding), the driver threads the resolved
    # align mode in as a static argument, and the single-device walk
    # slices the default-placed panel while each lane holds an
    # explicitly-pinned block — an unwarmed variant would pay compile
    # inside its timed wall and the "speedup" would measure the compiler,
    # not the mesh
    from spark_timeseries_tpu.models import base as _model_base

    walk_mode = _model_base.resolve_align_mode(panel)
    r = arima.fit(panel[:chunk_rows], order, align_mode=walk_mode)
    jax.block_until_ready(r.params)
    for d in lane_devs:
        r = arima.fit(jax.device_put(warm_host, d), order,
                      align_mode=walk_mode)
        jax.block_until_ready(r.params)
    del warm_host

    def _run(shard, ckpt):
        t0 = time.perf_counter()
        r = _rel.fit_chunked(arima.fit, panel, chunk_rows=chunk_rows,
                             resilient=False, order=order,
                             checkpoint_dir=ckpt, shard=shard,
                             mesh=mesh if shard else None)
        return r, time.perf_counter() - t0

    # telemetry rides BOTH walks (same instrumentation overhead on each
    # side of the speedup); for the sharded walk it also lands the
    # per-shard overlap in the merged manifest
    from spark_timeseries_tpu.reliability import faultinject as _fi

    def _run_degraded(ckpt):
        # ISSUE 11 acceptance: kill one lane mid-job (permanently — its
        # retries fail too, so it is QUARANTINED) and let the elastic
        # supervisor rebalance its chunks onto the survivors.  The fit is
        # the same compiled program; only lane 1's dispatches die.
        dead_fit = _fi.lane_kill(arima.fit, 1, after_chunks=1)
        t0 = time.perf_counter()
        r = _rel.fit_chunked(dead_fit, panel, chunk_rows=chunk_rows,
                             resilient=False, order=order,
                             checkpoint_dir=ckpt, shard=True, mesh=mesh)
        return r, time.perf_counter() - t0

    obs_was_on = _obs.enabled()
    if not obs_was_on:
        _obs.enable()
    try:
        r_single, wall_single = _run(False, tempfile.mkdtemp(
            prefix="sharded_ns_single_"))
        ckpt_sharded = tempfile.mkdtemp(prefix="sharded_ns_mesh_")
        r_sharded, wall_sharded = _run(True, ckpt_sharded)
        r_degraded, wall_degraded = _run_degraded(tempfile.mkdtemp(
            prefix="sharded_ns_degraded_"))
    finally:
        if not obs_was_on:
            _obs.disable()

    def _field_eq(r, f):
        a = np.asarray(getattr(r, f))
        b = np.asarray(getattr(r_single, f))
        return np.array_equal(a, b, equal_nan=a.dtype.kind == "f")

    fields = ("params", "neg_log_likelihood", "converged", "iters", "status")
    bitwise_ok = all(_field_eq(r_sharded, f) for f in fields)
    degraded_bitwise_ok = all(_field_eq(r_degraded, f) for f in fields)
    el = (r_degraded.meta.get("shards") or {}).get("elastic") or {}

    pipe = r_sharded.meta.get("pipeline") or {}
    per_shard = pipe.get("shards") or []
    shard_ov = [s.get("overlap_efficiency") for s in per_shard]
    shard_ov = [v for v in shard_ov if v is not None]
    j = r_sharded.meta.get("journal") or {}
    conv = float(np.sum(r_sharded.converged))
    return {
        "series_total": total,
        "obs_per_series": t,
        "n_lanes": n_lanes,
        "chunk_rows": chunk_rows,
        "chunks_per_lane": chunks_per_lane,
        "wall_s_sharded": round(wall_sharded, 3),
        "wall_s_single_device": round(wall_single, 3),
        # the acceptance number: >1x on >=2 local devices
        "sharded_speedup": (round(wall_single / wall_sharded, 4)
                            if wall_sharded > 0 else None),
        "sharded_converged_series_per_sec":
            round(conv / wall_sharded, 1) if wall_sharded > 0 else None,
        "converged_frac": round(conv / total, 4),
        "sharded_bitwise_identical": bitwise_ok,
        # degraded mode (ISSUE 11): 1 of n_lanes lanes killed mid-job and
        # quarantined; survivors rebalance its chunks.  The bar: losing a
        # lane must DEGRADE the mesh win, never erase it (> 1x vs the
        # single device), and the rebalance itself must stay cheap
        "wall_s_degraded": round(wall_degraded, 3),
        "degraded_speedup": (round(wall_single / wall_degraded, 4)
                             if wall_degraded > 0 else None),
        "rebalance_overhead": (round(wall_degraded / wall_sharded - 1.0, 4)
                               if wall_sharded > 0 else None),
        "degraded_bitwise_identical": degraded_bitwise_ok,
        "degraded_gate_ok": (wall_degraded > 0
                             and wall_single / wall_degraded > 1.0
                             and degraded_bitwise_ok),
        "quarantined_lanes": [q.get("shard_id")
                              for q in el.get("quarantined") or []],
        "degraded_steals": el.get("steals"),
        "overlap_efficiency": pipe.get("overlap_efficiency"),
        "input_overlap_efficiency": pipe.get("input_overlap_efficiency"),
        "per_shard_overlap_efficiency": shard_ov,
        "shard_overlap_efficiency_min": (round(min(shard_ov), 4)
                                         if shard_ov else None),
        "merged_manifest": {
            "dir": j.get("dir"),
            "merged_shards": j.get("merged_shards"),
            "chunks_resumed": j.get("chunks_resumed"),
        },
        "data": "same panel walked three times, all journaled: "
                "single-device vs series-sharded mesh vs DEGRADED mesh "
                "(lane 1 killed mid-job, quarantined, chunks rebalanced "
                "onto survivors); per-shard overlap journaled in the "
                "manifest telemetry",
    }


def _oversubscribed_northstar(jnp, order, quick, on_tpu):
    """ISSUE 7 acceptance: a journaled HOST-RESIDENT walk of a panel at
    least 2x the device memory budget it is allowed to hold resident.

    The SAME panel is walked twice through ``fit_chunked``, both
    journaled: once in-HBM (``jnp.asarray`` — every other PR's path, the
    ceiling) and once from host RAM through a ``HostChunkSource`` — each
    chunk staged H2D through the reusable staging pool, the staged buffer
    donated back as the walk passes.  Reported: the throughput ratio (the
    acceptance bar is >= 0.70 — input overlap must keep the H2D copies
    off the critical path), ``host_bitwise_identical`` (residency must
    not change a byte), and the donated-buffer device footprint
    (``peak_live_device_bytes``) against its O(chunk) bound — asserted
    via the staging accounting the memory probe now carries.

    The "device budget" is the allocator's ``bytes_limit`` where the
    backend reports one, capped at half the panel so the walk is ALWAYS
    oversubscribed >= 2x by construction (``virtual_budget: true`` marks
    a capped/absent limit — CPU runs and roomy chips both).
    """
    import tempfile

    import jax

    from spark_timeseries_tpu import obs as _obs
    from spark_timeseries_tpu import reliability as _rel
    from spark_timeseries_tpu.models import arima
    from spark_timeseries_tpu.obs.memory import peak_memory as _peak_mem

    if on_tpu and not quick:
        # the paper's time length at a panel big enough that the virtual
        # budget story is meaningful, small enough that host generation
        # does not dominate the bench (the H2D staging is the measurement)
        chunk_rows, t, n_chunks = 65_536, 1000, 8
    elif quick:
        chunk_rows, t, n_chunks = 256, 120, 4
    else:
        chunk_rows, t, n_chunks = 512, 200, 8
    total = chunk_rows * n_chunks
    chunk_bytes = chunk_rows * t * 4
    prefetch_depth = 2

    panel_host = gen_arima_panel(total, t, seed=13)
    panel_bytes = panel_host.nbytes

    try:
        limit = (jax.local_devices()[0].memory_stats() or {}).get(
            "bytes_limit")
    except Exception:  # noqa: BLE001 - CPU/odd backends: no stats
        limit = None
    virtual = not limit or limit > panel_bytes // 2
    budget = min(int(limit), panel_bytes // 2) if limit else panel_bytes // 2

    # warm both walks' one-time costs OUTSIDE the timed pair — the
    # chunk-shaped fit program, the host source's alias-breaking copy
    # program and first pool buffer, and the align plan (resolved once
    # and passed to BOTH walks as a hint, so neither pays a probe inside
    # its wall) — the pair then measures residency, not the compiler
    src = _rel.HostChunkSource(panel_host)
    walk_mode = src.align_mode()
    warm = src.stage(0, chunk_rows)
    r = arima.fit(warm, order, align_mode=walk_mode)
    jax.block_until_ready(r.params)
    del warm, r
    # ... and the journal/committer path itself (np.savez, manifest I/O,
    # obs instruments all pay first-use costs): one untimed 2-chunk
    # journaled walk, chunk-shaped so it reuses the warmed fit program
    _rel.fit_chunked(arima.fit, jnp.asarray(panel_host[:2 * chunk_rows]),
                     chunk_rows=chunk_rows, resilient=False, order=order,
                     align_mode=walk_mode,
                     checkpoint_dir=tempfile.mkdtemp(prefix="oversub_warm_"))

    def _run(values, ckpt):
        t0 = time.perf_counter()
        r = _rel.fit_chunked(arima.fit, values, chunk_rows=chunk_rows,
                             resilient=False, order=order,
                             prefetch_depth=prefetch_depth,
                             align_mode=walk_mode,
                             checkpoint_dir=ckpt)
        return r, time.perf_counter() - t0

    obs_was_on = _obs.enabled()
    if not obs_was_on:
        _obs.enable()
    try:
        panel_dev = jnp.asarray(panel_host)
        panel_dev.block_until_ready()
        # warm the in-HBM walk's per-boundary slice programs (static
        # start indices compile one program per chunk boundary — real but
        # amortized-to-nothing at production chunk counts, and it would
        # read as a residency difference at this bench's size)
        for wlo in range(0, total, chunk_rows):
            jax.block_until_ready(panel_dev[wlo:min(wlo + chunk_rows,
                                                    total)])
        r_hbm, wall_hbm = _run(panel_dev, tempfile.mkdtemp(
            prefix="oversub_hbm_"))
        del panel_dev  # the host walk must not lean on a resident copy
        ckpt_host = tempfile.mkdtemp(prefix="oversub_host_")
        r_host, wall_host = _run(src, ckpt_host)
    finally:
        if not obs_was_on:
            _obs.disable()

    def _field_eq(f):
        a = np.asarray(getattr(r_host, f))
        b = np.asarray(getattr(r_hbm, f))
        return np.array_equal(a, b, equal_nan=a.dtype.kind == "f")

    bitwise_ok = all(_field_eq(f) for f in (
        "params", "neg_log_likelihood", "converged", "iters", "status"))

    pipe = r_host.meta.get("pipeline") or {}
    pool = pipe.get("staging_pool") or {}
    peak_live = pool.get("peak_live_device_bytes")
    # O(chunk) bound: depth staged slices + the one computing + one in
    # transient handoff — NEVER the panel
    footprint_bound = (prefetch_depth + 2) * chunk_bytes
    conv = float(np.sum(r_host.converged))
    rate_host = conv / wall_host if wall_host > 0 else None
    rate_hbm = (float(np.sum(r_hbm.converged)) / wall_hbm
                if wall_hbm > 0 else None)
    pm = _peak_mem()
    return {
        "series_total": total,
        "obs_per_series": t,
        "chunks": n_chunks,
        "panel_bytes": panel_bytes,
        "device_budget_bytes": budget,
        "virtual_budget": bool(virtual),
        "oversubscription_factor": round(panel_bytes / budget, 2),
        "wall_s_host_resident": round(wall_host, 3),
        "wall_s_in_hbm": round(wall_hbm, 3),
        "host_converged_series_per_sec": (round(rate_host, 1)
                                          if rate_host else None),
        "in_hbm_converged_series_per_sec": (round(rate_hbm, 1)
                                            if rate_hbm else None),
        # the acceptance number: sustained host-resident throughput as a
        # fraction of the in-HBM ceiling (bar: >= 0.70)
        "host_over_hbm_throughput": (round(rate_host / rate_hbm, 4)
                                     if rate_host and rate_hbm else None),
        "host_bitwise_identical": bitwise_ok,
        "converged_frac": round(conv / total, 4),
        # the O(chunk) footprint contract, from the donated-buffer
        # accounting (reliability.source): staged device bytes alive at
        # once, vs the bound the walk promises
        "device_footprint_bytes_peak": peak_live,
        "device_footprint_bound_bytes": footprint_bound,
        "device_footprint_ok": (peak_live is not None
                                and peak_live <= footprint_bound),
        "input_overlap_efficiency": pipe.get("input_overlap_efficiency"),
        "staging_pool": pool,
        "peak_mem_bytes": pm.bytes,
        "peak_mem_source": pm.source,
        "staging_pool_peak_host_bytes": pm.staging_pool_bytes,
        "journal": {"dir": ckpt_host},
        "data": "same panel walked twice, both journaled: in-HBM "
                "(jnp.asarray ceiling) vs host-resident "
                "(HostChunkSource: pooled staging buffers, async H2D "
                "prefetch, donated device buffers); device peak bounded "
                "by O(chunk), never O(panel)",
    }


def _auto_fit_northstar(jnp, quick, on_tpu):
    """ISSUE 9/10 acceptance: batched order search throughput — fitting a
    GRID of candidate orders per series at far less than G independent
    full-fit campaigns.

    One journaled FUSED ``models.auto.auto_fit`` (same-d orders batched
    into one walk each, ISSUE 10) over an ARIMA(1,1,1) panel and a
    G-candidate grid, telemetry on, plus a journaled ``fuse=1`` per-order
    search over the same panel/grid so the fusion win is a measured ratio
    (``fused_speedup``).  Reported: **candidate-orders x series/sec**
    (grid cells per second — the number this workload's users buy), the
    program-reuse rate from the ``compile_cache.hit``/``miss`` counters,
    the shared-prep savings (``diff_cache_hits`` — differencings the
    fused groups never re-ran), the fused-vs-per-order selection
    agreement, and — from a ``stage2="winners"`` search over the same
    panel — the repaired economy's speedup (now GATED at >= 1: PR 8
    shipped it 18x slower) and its selection agreement with the exact
    search.  Both searches and the winners pass are compile-warmed
    outside the timed region (matching every other north-star: the timed
    wall measures the walk, the hit-rate metric reports reuse).
    Selection correctness is gated in tier-1 (tests/test_auto.py): fused
    agrees with per-order, and ``fuse=1`` is bitwise the exhaustive
    argmin; the bench measures speed, not re-proves correctness.
    """
    import tempfile

    import jax

    from spark_timeseries_tpu import obs as _obs
    from spark_timeseries_tpu.models import auto as _auto
    from spark_timeseries_tpu.models import arima as _arima_mod

    if on_tpu and not quick:
        b, t, chunk_rows = 131_072, 1000, 32_768
        orders = [(1, 0, 0), (0, 0, 1), (1, 0, 1), (0, 1, 1), (1, 1, 0),
                  (1, 1, 1), (2, 1, 1), (1, 1, 2)]
        max_iters = 60
    elif quick:
        b, t, chunk_rows = 256, 120, 128
        orders = [(1, 0, 0), (0, 1, 1), (1, 1, 1)]
        max_iters = 20
    else:
        b, t, chunk_rows = 1024, 200, 256
        orders = [(1, 0, 0), (0, 0, 1), (0, 1, 1), (1, 1, 0), (1, 1, 1)]
        max_iters = 25
    g = len(orders)
    panel = jnp.asarray(gen_arima_panel(b, t, seed=21))
    panel.block_until_ready()

    # every timed search is preceded by one JOURNALED warm pass of the
    # same mode into a scratch dir: compiles, allocator/runtime warmup,
    # AND the journal I/O path (first fsyncs, tempfile machinery) land
    # outside the timed wall, so fused vs per-order is a
    # steady-state-vs-steady-state ratio, not an artifact of which search
    # ran first (compile spend is reported separately by the hit-rate
    # metric, matching every other north-star's warm-both-sides
    # discipline)
    s1_iters = max(6, max_iters // 4)

    obs_was_on = _obs.enabled()
    if not obs_was_on:
        _obs.enable()
    try:
        _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                       max_iters=max_iters,
                       checkpoint_dir=tempfile.mkdtemp(prefix="auto_w_"))
        c0 = (_obs.snapshot() or {}).get("counters", {})
        ckpt = tempfile.mkdtemp(prefix="auto_ns_")
        t0 = time.perf_counter()
        res = _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                             max_iters=max_iters, checkpoint_dir=ckpt)
        wall = time.perf_counter() - t0
        c1 = (_obs.snapshot() or {}).get("counters", {})
        # fuse=1 baseline: the PR 8 per-order walks, same panel/grid —
        # fused_speedup is the tentpole's measured win
        _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                       max_iters=max_iters, fuse=1,
                       checkpoint_dir=tempfile.mkdtemp(prefix="auto_w1_"))
        ckpt1 = tempfile.mkdtemp(prefix="auto_ns_f1_")
        t0 = time.perf_counter()
        res_1 = _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                               max_iters=max_iters, checkpoint_dir=ckpt1,
                               fuse=1)
        wall_1 = time.perf_counter() - t0
        # winners economy: the warm pass also compiles the basin-refit
        # programs (their cap shapes depend on the selection, so they
        # cannot be warmed up front), then the timed steady-state pass
        _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                       max_iters=max_iters, stage2="winners",
                       stage1_iters=s1_iters)
        t0 = time.perf_counter()
        res_w = _auto.auto_fit(panel, orders, chunk_rows=chunk_rows,
                               max_iters=max_iters, stage2="winners",
                               stage1_iters=s1_iters)
        wall_w = time.perf_counter() - t0
    finally:
        if not obs_was_on:
            _obs.disable()

    cc_hits = c1.get("compile_cache.hit", 0) - c0.get("compile_cache.hit", 0)
    cc_miss = (c1.get("compile_cache.miss", 0)
               - c0.get("compile_cache.miss", 0))
    am = res.meta["auto_fit"]
    am_w = res_w.meta["auto_fit"]
    agree = float(np.mean(np.asarray(res_w.order_index)
                          == np.asarray(res.order_index)))
    agree_fused = float(np.mean(np.asarray(res_1.order_index)
                                == np.asarray(res.order_index)))
    conv = float(np.sum(res.converged))
    top = sorted(((k2, v) for k2, v in am["selection_counts"].items()
                  if k2 != "none"), key=lambda kv: -kv[1])[:3]
    winners_speedup = round(wall / wall_w, 4) if wall_w > 0 else None
    return {
        "series_total": b,
        "obs_per_series": t,
        "candidate_orders": g,
        "chunk_rows": chunk_rows,
        "wall_s": round(wall, 3),
        # the acceptance number: grid cells fitted per second — G
        # candidates per series, so the search throughput in full-fit
        # equivalents (the FUSED search: same-d orders share one walk)
        "order_series_per_sec": round(g * b / wall, 1) if wall > 0 else None,
        "selected_series_per_sec": round(b / wall, 1) if wall > 0 else None,
        "converged_frac": round(conv / b, 4),
        "selection_top": dict(top),
        "selection_none": am["selection_counts"].get("none", 0),
        # ISSUE 10 tentpole: fused walk count + measured win over the
        # per-order search, with the shared-prep differencing savings
        "fusion_groups": len(am["fusion_groups"]),
        "diff_cache_hits": am["diff_cache_hits"],
        "per_order_wall_s": round(wall_1, 3),
        "fused_speedup": round(wall_1 / wall, 4) if wall > 0 else None,
        "fused_selection_agreement": round(agree_fused, 4),
        # per-walk compiled-program reuse, measured (ISSUE 9 satellite):
        # with C chunks per walk the steady state is (C-1)/C hits
        "compile_cache_hit_rate": (round(cc_hits / (cc_hits + cc_miss), 4)
                                   if (cc_hits + cc_miss) else None),
        "compile_cache_hits": cc_hits,
        "compile_cache_misses": cc_miss,
        # stage-2 spend: zero for the exact search (the lazy split only
        # dispatches stage 2 when stragglers remain); the winners pass
        # reports the economy's spend share and its agreement
        "stage2_spend_share": am["stage2_spend_share"],
        "winners_wall_s": round(wall_w, 3),
        "winners_speedup": winners_speedup,
        # ISSUE 10 winners repair: the economy mode must actually be an
        # economy — PR 8 silently shipped it 18x SLOWER (0.0538)
        "winners_gate_ok": (winners_speedup is not None
                            and winners_speedup >= 1.0),
        "winners_stage2_spend_share": am_w["stage2_spend_share"],
        "winners_selection_agreement": round(agree, 4),
        "journal": {"dir": ckpt},
        "data": "journaled fused exact search (same-d orders batched into "
                "one walk each, on-device AICc argmin) vs a journaled "
                "fuse=1 per-order search, + an unjournaled "
                "stage2='winners' economy pass (warm-started per-basin "
                "batched refits; timed after one compile pass) over the "
                "same panel/grid",
    }


def _serving_northstar(jnp, quick, on_tpu):
    """ISSUE 12 acceptance: the resident serving loop under load.

    Drives a :class:`serving.FitServer` with a concurrent multi-tenant
    request storm and reports what a service owner buys: sustained
    **request throughput and p50/p99 request latency** (client-measured,
    submit -> demuxed result), the **batching amplification** (the same
    storm against a coalescing-disabled server — how much the
    micro-batched walk beats per-request walks), and the **overload
    contract** at 2x queue capacity: the server SHEDS with explicit
    rejections and answers everything else — zero OOMs, zero hangs,
    conservation of requests (floor-gated ``serving_gate_ok``; the
    bitwise batched==solo and crash-recovery contracts are tier-1 tests,
    not re-proved here).  Both servers journal every batch (the serving
    path IS the durable path) and run compile-warmed via a scratch
    warm-up request, so the measured walls are steady-state serving, not
    first-compile.
    """
    import tempfile
    import threading

    from spark_timeseries_tpu import serving

    if on_tpu and not quick:
        n_reqs, rows, t_len, iters = 32, 8192, 1000, 60
    elif quick:
        n_reqs, rows, t_len, iters = 6, 16, 120, 15
    else:
        n_reqs, rows, t_len, iters = 16, 64, 200, 25
    kw = dict(order=(1, 1, 1), max_iters=iters)
    panel = gen_arima_panel(n_reqs * rows, t_len, seed=33)
    panels = [np.ascontiguousarray(panel[i * rows:(i + 1) * rows])
              for i in range(n_reqs)]

    def _drive(srv, reqs, timeout=1800.0):
        lat = [None] * len(reqs)
        errs = [None] * len(reqs)

        def one(i):
            t0 = time.perf_counter()
            try:
                tk = srv.submit(f"tenant-{i}", reqs[i], "arima", **kw)
                tk.result(timeout=timeout)
                lat[i] = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 - per-request record
                errs[i] = e

        ts = [threading.Thread(target=one, args=(i,), daemon=True)
              for i in range(len(reqs))]
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=timeout)
        return time.perf_counter() - t0, lat, errs

    def _mk(root, **over):
        cfg = dict(cell_rows=rows, batch_window_s=0.01,
                   max_batch_rows=max(rows * 8, rows), autotune=False,
                   max_queue_rows=n_reqs * rows * 4,
                   max_queue_requests=4 * n_reqs + 8)
        cfg.update(over)
        return serving.FitServer(root, **cfg)

    # warm-up: one batch through a scratch server compiles the cell
    # program + journal path for every later server (process-wide caches)
    with _mk(tempfile.mkdtemp(prefix="srvns_warm_")) as warm:
        warm.submit("warm", panels[0], "arima", **kw).result(timeout=1800)

    # 1. sustained storm, coalescing ON
    with _mk(tempfile.mkdtemp(prefix="srvns_b_")) as srv:
        wall_b, lat_b, errs_b = _drive(srv, panels)
        batched_counters = srv.health()["counters"]
    # 2. the same storm, coalescing OFF (every batch = one request)
    with _mk(tempfile.mkdtemp(prefix="srvns_s_"), batch_window_s=0.0,
             max_batch_rows=rows) as srv:
        wall_s, _lat_s, errs_s = _drive(srv, panels)
        solo_batches = srv.health()["counters"]["batches_run"]
    # 3. 2x overload: the queue holds half the storm's rows — the rest
    #    must shed with explicit rejections, never an OOM or a hang
    storm = panels + panels  # 2x the sustained load
    with _mk(tempfile.mkdtemp(prefix="srvns_o_"),
             max_queue_rows=max(rows, (n_reqs * rows) // 2),
             batch_window_s=0.0) as srv:
        wall_o, lat_o, errs_o = _drive(srv, storm)
        over_counters = srv.health()["counters"]
    served_o = sum(1 for e in lat_o if e is not None)
    rejected_o = sum(1 for e in errs_o
                     if isinstance(e, serving.RejectedError))
    other_errs = [e for e in errs_o
                  if e is not None
                  and not isinstance(e, serving.RejectedError)]
    conserved = served_o + rejected_o == len(storm)
    shed_rate = rejected_o / len(storm)
    lats = sorted(v for v in lat_b if v is not None)
    ok_b = not any(errs_b) and not any(errs_s) and len(lats) == n_reqs
    gate_ok = bool(ok_b and conserved and rejected_o > 0
                   and not other_errs)
    return {
        "requests": n_reqs,
        "rows_per_request": rows,
        "obs_per_series": t_len,
        "cell_rows": rows,
        "wall_s": round(wall_b, 3),
        "rows_per_sec": (round(n_reqs * rows / wall_b, 1)
                         if wall_b > 0 else None),
        "requests_per_sec": (round(n_reqs / wall_b, 2)
                             if wall_b > 0 else None),
        "p50_request_latency_s": (round(float(np.percentile(lats, 50)), 4)
                                  if lats else None),
        "p99_request_latency_s": (round(float(np.percentile(lats, 99)), 4)
                                  if lats else None),
        "batches_run": batched_counters["batches_run"],
        "solo_wall_s": round(wall_s, 3),
        "solo_batches": solo_batches,
        # >1: the coalescing walk beats one-walk-per-request on the same
        # storm (fewer walks, shared staging pool, reused programs)
        "batch_amplification": (round(wall_s / wall_b, 4)
                                if wall_b > 0 else None),
        "overload_submitted": len(storm),
        "overload_served": served_o,
        "overload_rejected": rejected_o,
        "overload_shed_rate": round(shed_rate, 4),
        "overload_conserved": conserved,
        "overload_other_errors": [repr(e)[:120] for e in other_errs[:3]],
        "overload_wall_s": round(wall_o, 3),
        # the floor gate: overload degrades to explicit shedding with
        # every other request answered — never an OOM, never a hang
        "serving_gate_ok": gate_ok,
        "data": "resident FitServer; concurrent storm of "
                f"{n_reqs} tenant requests x {rows} rows (journaled "
                "micro-batched walks, warm staging pool/compile cache) "
                "vs the same storm with coalescing disabled, + a 2x "
                "overload storm against a half-sized admission queue",
    }


def _fleet_serving_northstar(jnp, quick, on_tpu):
    """ISSUE 16 acceptance: the fleet behind a socket.

    Drives a 2-replica :class:`serving.fleet.FleetReplica` fleet (one
    shared checkpoint root, lease-fenced) through the length-prefixed
    wire protocol with a concurrent :class:`FitClient` request storm and
    reports what a fleet operator buys: sustained **through-the-wire
    request throughput and p50/p99 latency** (client-measured, socket
    included), and the **failover-recovery latency** — a doomed primary
    crashes mid-batch after its first durable commit, the standby takes
    the lease over, and the SAME in-flight request is re-answered
    through the client's poll loop; the penalty over the steady-state
    p50 is the price of a failover.  The re-answer must be bitwise an
    uninterrupted server's (floor-gated ``fleet_gate_ok`` together with
    storm conservation and the lease landing on the survivor).
    """
    import tempfile
    import threading

    from spark_timeseries_tpu import obs as _obs
    from spark_timeseries_tpu import serving
    from spark_timeseries_tpu.reliability import faultinject as fi
    from spark_timeseries_tpu.reliability.journal import read_lease
    from spark_timeseries_tpu.serving.client import FitClient
    from spark_timeseries_tpu.serving.fleet import (FleetReplica,
                                                    discover_endpoints)

    if on_tpu and not quick:
        n_reqs, rows, t_len, iters = 32, 8192, 1000, 60
    elif quick:
        n_reqs, rows, t_len, iters = 6, 16, 120, 15
    else:
        n_reqs, rows, t_len, iters = 12, 64, 200, 25
    kw = dict(order=(1, 1, 1), max_iters=iters)
    panel = gen_arima_panel(n_reqs * rows, t_len, seed=47)
    panels = [np.ascontiguousarray(panel[i * rows:(i + 1) * rows])
              for i in range(n_reqs)]
    srv_kw = dict(cell_rows=rows, batch_window_s=0.01,
                  max_batch_rows=max(rows * 8, rows), autotune=False,
                  max_queue_rows=n_reqs * rows * 4,
                  max_queue_requests=4 * n_reqs + 8)
    fields = ("params", "neg_log_likelihood", "converged", "iters",
              "status")

    # warm-up: compile the cell program once, process-wide
    with serving.FitServer(tempfile.mkdtemp(prefix="fleetns_warm_"),
                           **srv_kw) as warm:
        warm.submit("warm", panels[0], "arima", **kw).result(timeout=1800)

    def _storm(cli, reqs, prefix, timeout=1800.0):
        lat = [None] * len(reqs)
        errs = [None] * len(reqs)

        def one(i):
            t0 = time.perf_counter()
            try:
                tk = cli.submit(f"{prefix}-{i}", reqs[i], "arima",
                                request_id=f"{prefix}-{i}", **kw)
                tk.result(timeout=timeout)
                lat[i] = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 - per-request record
                errs[i] = e

        ts = [threading.Thread(target=one, args=(i,), daemon=True)
              for i in range(len(reqs))]
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=timeout)
        return time.perf_counter() - t0, lat, errs

    # 1. sustained storm THROUGH THE WIRE against a 2-replica fleet
    root = tempfile.mkdtemp(prefix="fleetns_storm_")
    with FleetReplica(root, owner="p", ttl_s=2.0,
                      server_kwargs=srv_kw) as p:
        p.wait_role("primary", 600)
        with FleetReplica(root, owner="s", ttl_s=2.0,
                          server_kwargs=srv_kw):
            cli = FitClient(discover_endpoints(root), seed=5,
                            deadline_s=1800.0)
            wall_b, lat_b, errs_b = _storm(cli, panels, "req")
            cli.close()
            # obs_overhead leg (ISSUE 18): the same storm with the
            # telemetry plane ON — recorder stream + trace stamping on
            # every event, client and in-process replicas alike.  Fresh
            # request ids so the idempotent cache does not short-circuit
            # the work; the traced/untraced throughput ratio is the
            # price of fleet-wide tracing, floor-gated so it can never
            # silently eat half the throughput.
            obs_was_on = _obs.enabled()
            if not obs_was_on:
                _obs.enable(os.path.join(root, "obs_client.jsonl"))
            try:
                cli_t = FitClient(discover_endpoints(root), seed=5,
                                  deadline_s=1800.0)
                wall_t, lat_t, errs_t = _storm(cli_t, panels, "treq")
                cli_t.close()
            finally:
                if not obs_was_on:
                    _obs.disable()
    lats = sorted(v for v in lat_b if v is not None)
    storm_ok = not any(errs_b) and len(lats) == n_reqs
    p50 = float(np.percentile(lats, 50)) if lats else None
    traced_ok = not any(errs_t) and all(v is not None for v in lat_t)
    obs_ratio = (round(wall_b / wall_t, 3)
                 if traced_ok and wall_b > 0 and wall_t > 0 else None)

    # 2. failover-recovery latency: primary crashes mid-batch after its
    #    first durable commit; the standby takes over and re-answers
    root2 = tempfile.mkdtemp(prefix="fleetns_fail_")
    a = FleetReplica(root2, owner="a", ttl_s=1.0, retire_on_crash=True,
                     server_kwargs=dict(
                         srv_kw, _commit_hook=fi.crash_after_commits(1)))
    b = FleetReplica(root2, owner="b", ttl_s=1.0, server_kwargs=srv_kw)
    with a, b:
        a.wait_role("primary", 600)
        cli = FitClient(discover_endpoints(root2), seed=6,
                        deadline_s=1800.0)
        t0 = time.perf_counter()
        got = cli.submit("fo", panels[0], "arima", request_id="fo-1",
                         **kw).result(timeout=1800)
        failover_wall = time.perf_counter() - t0
        took_over = b.wait_role("primary", 600)
        elections = b.counters["elections"]
        survivor_holds = (read_lease(root2) or {}).get("owner") == "b"
        cli.close()
    with serving.FitServer(tempfile.mkdtemp(prefix="fleetns_ref_"),
                           **srv_kw) as ref:
        want = ref.submit("fo", panels[0], "arima", request_id="fo-1",
                          **kw).result(timeout=1800)
    bitwise = all(
        np.array_equal(np.asarray(getattr(got, f)),
                       np.asarray(getattr(want, f)), equal_nan=True)
        for f in fields)
    gate_ok = bool(storm_ok and took_over and bitwise and survivor_holds)
    return {
        "replicas": 2,
        "requests": n_reqs,
        "rows_per_request": rows,
        "obs_per_series": t_len,
        "wall_s": round(wall_b, 3),
        "rows_per_sec": (round(n_reqs * rows / wall_b, 1)
                         if wall_b > 0 else None),
        "requests_per_sec": (round(n_reqs / wall_b, 2)
                             if wall_b > 0 else None),
        "p50_request_latency_s": (round(p50, 4)
                                  if p50 is not None else None),
        "p99_request_latency_s": (round(float(np.percentile(lats, 99)), 4)
                                  if lats else None),
        "storm_errors": [repr(e)[:120] for e in errs_b if e][:3],
        # submit -> re-answered THROUGH a primary crash + lease takeover;
        # the penalty over steady-state p50 is the failover price
        "failover_request_wall_s": round(failover_wall, 3),
        "failover_recovery_penalty_s": (round(failover_wall - p50, 3)
                                        if p50 is not None else None),
        "failover_bitwise_identical": bitwise,
        "failover_elections": elections,
        # traced-storm throughput over untraced (ISSUE 18): < 1 means
        # tracing costs; the regression gate floors it at 0.5
        "obs_overhead_ratio": obs_ratio,
        "obs_overhead_wall_s": round(wall_t, 3),
        "fleet_gate_ok": gate_ok,
        "data": "2 FleetReplica on one lease-fenced root; socket storm "
                f"of {n_reqs} tenant requests x {rows} rows through "
                "FitClient (length-prefixed frames, idempotent ids), + "
                "a crash-mid-batch failover leg re-answered by the "
                "surviving standby",
    }


def _chaos_northstar(jnp, quick, on_tpu):
    """ISSUE 17 acceptance: graceful degradation under chaos.

    Measures what the degradation ladder buys a fleet operator: **read
    availability through a primary crash** and **degraded-read
    throughput** off a standby that never holds the lease.  A 2-replica
    fleet serves a committed result; a probe loop reads it continuously
    while the primary is killed mid-request (``crash_after_commits``);
    standby reads must keep the probes answering through the leaderless
    window, so the longest unavailability window is the headline.  After
    the takeover a THIRD replica joins as a standby and a client pinned
    to it alone measures reads/sec from durable files — and must be
    refused on a write.  ``chaos_gate_ok`` floors the availability bound
    together with both bitwise contracts and the write refusal.
    """
    import tempfile
    import threading

    from spark_timeseries_tpu import serving
    from spark_timeseries_tpu.reliability import chaos
    from spark_timeseries_tpu.reliability import faultinject as fi
    from spark_timeseries_tpu.reliability.journal import read_lease
    from spark_timeseries_tpu.serving.client import FitClient
    from spark_timeseries_tpu.serving.fleet import (FleetReplica,
                                                    discover_endpoints)

    if on_tpu and not quick:
        rows, t_len, iters, n_reads = 1024, 500, 60, 200
    elif quick:
        rows, t_len, iters, n_reads = 16, 120, 15, 40
    else:
        rows, t_len, iters, n_reads = 64, 200, 25, 100
    kw = dict(order=(1, 1, 1), max_iters=iters)
    panel = gen_arima_panel(rows, t_len, seed=53)
    srv_kw = dict(cell_rows=rows, batch_window_s=0.01, autotune=False)
    fields = ("params", "neg_log_likelihood", "converged", "iters",
              "status")
    ttl = 1.0
    probe_period_s = 0.05
    max_unavailable_s = 5.0  # bound >> the longest expected leaderless gap

    def _bitwise(got, want):
        return all(
            np.array_equal(np.asarray(getattr(got, f)),
                           np.asarray(getattr(want, f)), equal_nan=True)
            for f in fields)

    # reference answers from an uninterrupted single server (also warms
    # the cell program process-wide)
    with serving.FitServer(tempfile.mkdtemp(prefix="chaosns_ref_"),
                           **srv_kw) as ref:
        want_seed = ref.submit("seed", panel, "arima", request_id="seed-0",
                               **kw).result(timeout=1800)
        want_kill = ref.submit("kill", panel, "arima", request_id="kill-1",
                               **kw).result(timeout=1800)

    root = tempfile.mkdtemp(prefix="chaosns_")
    # commit 1 is seed-0 (survives durably); commit 2 is kill-1 — the
    # primary crashes right after committing it, mid-reply
    a = FleetReplica(root, owner="a", ttl_s=ttl, retire_on_crash=True,
                     server_kwargs=dict(
                         srv_kw, _commit_hook=fi.crash_after_commits(2)))
    b = FleetReplica(root, owner="b", ttl_s=ttl, server_kwargs=srv_kw)
    probes = []
    with a, b:
        a.wait_role("primary", 600)
        cli = FitClient(discover_endpoints(root), seed=7,
                        deadline_s=1800.0, failure_threshold=2,
                        hedge_after_s=0.75)
        got_seed = cli.submit("seed", panel, "arima", request_id="seed-0",
                              **kw).result(timeout=1800)

        stop = threading.Event()
        t00 = time.perf_counter()

        def _probe_loop():
            while not stop.is_set():
                try:
                    r = cli.result_for("seed-0", timeout=2.0)
                    ok = r is not None
                except Exception:  # noqa: BLE001 - a probe miss IS the datum
                    ok = False
                probes.append((time.perf_counter() - t00, bool(ok)))
                stop.wait(probe_period_s)

        th = threading.Thread(target=_probe_loop, daemon=True)
        th.start()
        t0 = time.perf_counter()
        got_kill = cli.submit("kill", panel, "arima", request_id="kill-1",
                              **kw).result(timeout=1800)
        failover_wall = time.perf_counter() - t0
        took_over = b.wait_role("primary", 600)
        stop.wait(2 * ttl)  # keep probing past the takeover
        stop.set()
        th.join(timeout=60)
        survivor_holds = (read_lease(root) or {}).get("owner") == "b"
        cli.close()

        # degraded-read leg: a THIRD replica joins as a standby; a client
        # pinned to it alone reads the committed result from durable
        # files without the lease ever moving
        with FleetReplica(root, owner="c", ttl_s=ttl,
                          server_kwargs=srv_kw) as c:
            c.wait_role("standby", 600)
            rcli = FitClient([c.address], seed=8, deadline_s=1800.0,
                             retries=2, backoff_base_s=0.01)
            first = rcli.result_for("seed-0", timeout=60)
            sb_bitwise = first is not None and _bitwise(first, want_seed)
            td = time.perf_counter()
            for _ in range(n_reads):
                rcli.result_for("seed-0", timeout=60)
            degraded_wall = time.perf_counter() - td
            standby_reads = c.counters["standby_reads"]
            try:
                rcli.submit("nope", panel, "arima", request_id="nope-1",
                            **kw)
                write_refused = False
            except Exception:  # noqa: BLE001 - the refusal IS the contract
                write_refused = True
            rcli.close()

    windows = chaos.unavailability_windows(probes)
    longest = max((e - s for s, e in windows), default=0.0)
    ok_rate = (sum(1 for _, ok in probes if ok) / len(probes)
               if probes else 0.0)
    kill_bitwise = _bitwise(got_kill, want_kill)
    gate_ok = bool(took_over and survivor_holds and kill_bitwise
                   and _bitwise(got_seed, want_seed) and sb_bitwise
                   and write_refused and longest <= max_unavailable_s
                   and ok_rate >= 0.8)
    return {
        "replicas": 3,
        "rows_per_request": rows,
        "obs_per_series": t_len,
        "probes": len(probes),
        "probe_period_s": probe_period_s,
        "probe_ok_rate": round(ok_rate, 4),
        "longest_unavailable_s": round(longest, 3),
        "unavailability_windows": len(windows),
        "max_unavailable_s": max_unavailable_s,
        "failover_request_wall_s": round(failover_wall, 3),
        "failover_bitwise_identical": kill_bitwise,
        "standby_read_bitwise": sb_bitwise,
        "degraded_reads_per_sec": (round(n_reads / degraded_wall, 1)
                                   if degraded_wall > 0 else None),
        "standby_reads_served": standby_reads,
        "write_refused_on_standby": write_refused,
        "chaos_gate_ok": gate_ok,
        "data": "2 FleetReplica + a late-joining standby on one "
                "lease-fenced root; a committed result probed every "
                f"{probe_period_s}s through a crash-mid-request primary "
                "kill (standby reads cover the leaderless window), then "
                f"{n_reads} reads off the standby alone",
    }


def _forecast_northstar(jnp, quick, on_tpu):
    """ISSUE 14 acceptance: the panel-scale forecast surface behind the
    long-dormant ``forecast_latency_s`` field.

    Fits a panel once (journaled), then measures what
    fit-once/forecast-many actually serves: **journaled panel forecast
    throughput** (rows/sec through the chunked forecast walk, intervals
    on), **resume identity** (the same walk re-run on its journal must
    rehydrate bitwise — and a forecast from the fit JOURNAL must equal
    the forecast from the in-memory fit result), a **rolling-origin
    backtest campaign wall** (3 expanding windows, warm-started refits,
    MAE/coverage into a durable manifest), and the **ensemble overhead**
    (criterion-weighted 2-member blend vs the per-member forecast walls,
    with temperature->0 recovering the argmin winner bitwise).  The
    bitwise flags are floor-gated in the telemetry regression gate.
    """
    import tempfile

    from spark_timeseries_tpu import forecasting as fcast
    from spark_timeseries_tpu import reliability as rel
    from spark_timeseries_tpu.models import arima as _arima

    if on_tpu and not quick:
        b, t_len, horizon, iters, n_samples = 65_536, 1000, 28, 60, 128
    elif quick:
        b, t_len, horizon, iters, n_samples = 64, 120, 8, 15, 32
    else:
        b, t_len, horizon, iters, n_samples = 512, 200, 12, 25, 64
    order = (1, 0, 1)
    chunk_rows = max(64, b // 8)
    y = gen_arima_panel(b, t_len, seed=44)
    root = tempfile.mkdtemp(prefix="fcns_")
    fit_dir = os.path.join(root, "fit")
    fit_res = rel.fit_chunked(
        _arima.fit, jnp.asarray(y), chunk_rows=chunk_rows,
        resilient=False, order=order, max_iters=iters,
        checkpoint_dir=fit_dir)
    kw = dict(model_kwargs={"order": order}, intervals=True,
              n_samples=n_samples, chunk_rows=chunk_rows)
    # warm the compiled programs on a small slice so the timed walk
    # measures execution + journaling, not tracing
    fcast.forecast_chunked("arima", np.asarray(fit_res.params)[:chunk_rows],
                           y[:chunk_rows], horizon, model_kwargs={
                               "order": order}, intervals=True,
                           n_samples=n_samples, chunk_rows=chunk_rows)
    fc_dir = os.path.join(root, "fc")
    t0 = time.perf_counter()
    fc1 = fcast.forecast_chunked("arima", fit_res, jnp.asarray(y), horizon,
                                 checkpoint_dir=fc_dir, **kw)
    fc_wall = time.perf_counter() - t0
    # resume the SAME walk (all chunks rehydrate) + forecast straight
    # from the fit journal: both must be bitwise
    fc2 = fcast.forecast_chunked("arima", fit_res, jnp.asarray(y), horizon,
                                 checkpoint_dir=fc_dir, **kw)
    fc3 = fcast.forecast_chunked("arima", fit_dir, jnp.asarray(y), horizon,
                                 **kw)
    bitwise = all(
        np.array_equal(getattr(fc1, f), getattr(o, f), equal_nan=True)
        for o in (fc2, fc3) for f in ("forecast", "lo", "hi"))
    resumed = fc2.meta["journal"]["chunks_resumed"]

    # rolling-origin backtest campaign (smaller panel off-TPU: W refits)
    bt_rows = min(b, 4096 if on_tpu and not quick else 128)
    t0 = time.perf_counter()
    bt = fcast.run_backtest(
        y[:bt_rows], "arima", horizon, model_kwargs={"order": order},
        fit_kwargs={"max_iters": iters}, n_windows=3,
        chunk_rows=min(chunk_rows, bt_rows), intervals=True,
        n_samples=n_samples, checkpoint_dir=os.path.join(root, "bt"))
    bt_wall = time.perf_counter() - t0

    # criterion-weighted ensemble: 2 members over the backtest slice
    ens_rows = bt_rows
    t0 = time.perf_counter()
    ens = fcast.ensemble_forecast(
        y[:ens_rows], horizon, orders=[(1, 0, 0), order],
        temperature=1.0, chunk_rows=min(chunk_rows, ens_rows),
        fit_kwargs={"max_iters": iters})
    ens_wall = time.perf_counter() - t0
    ens0 = fcast.ensemble_forecast(
        y[:ens_rows], horizon, orders=[(1, 0, 0), order],
        temperature=0.0, chunk_rows=min(chunk_rows, ens_rows),
        fit_kwargs={"max_iters": iters})
    rows_idx = np.arange(ens_rows)
    argmin_ok = bool(np.array_equal(
        ens0.forecast, ens0.member_forecasts[ens0.order_index, rows_idx],
        equal_nan=True))
    weights_ok = bool(np.allclose(
        ens.weights.sum(0)[ens.order_index >= 0], 1.0))
    # overhead of blending vs just forecasting each member once
    per_member = fc_wall * (ens_rows / b) if b else None
    ens_overhead = (round(ens_wall / max(2 * per_member, 1e-9), 4)
                    if per_member else None)
    coverage = (bt.metrics.get("coverage_h") or [None])[0]
    gate_ok = bool(bitwise and argmin_ok and weights_ok
                   and bt.meta["windows_committed"] == 3)
    return {
        "series_total": b,
        "obs_per_series": t_len,
        "horizon": horizon,
        "intervals_n_samples": n_samples,
        "forecast_wall_s": round(fc_wall, 3),
        "forecast_rows_per_sec": (round(b / fc_wall, 1)
                                  if fc_wall > 0 else None),
        "forecast_values_per_sec": (round(b * horizon / fc_wall, 1)
                                    if fc_wall > 0 else None),
        "forecast_bitwise_identical": bool(bitwise),
        "forecast_chunks_resumed": resumed,
        "backtest_windows": bt.meta["windows_committed"],
        "backtest_rows": bt_rows,
        "backtest_wall_s": round(bt_wall, 3),
        "backtest_coverage_h1": coverage,
        "ensemble_wall_s": round(ens_wall, 3),
        "ensemble_overhead": ens_overhead,
        "ensemble_weights_sum_ok": weights_ok,
        "ensemble_argmin_bitwise": argmin_ok,
        "forecast_gate_ok": gate_ok,
        "data": f"journaled panel forecast walk ({b} series x {t_len} "
                f"obs -> {horizon} steps, MC intervals) + resume/"
                "from-journal bitwise + 3-window rolling-origin backtest "
                "campaign + 2-member criterion-weighted ensemble",
    }


def _delta_refit_northstar(jnp, quick, on_tpu):
    """ISSUE 15 acceptance: tick-to-fit — refit cost vs fraction touched.

    The target scenario is a market-data feed mutating a fitted panel.
    Two legs, both journaled and both proven bitwise:

    - **10%-dirty delta** (the floor-gated headline): fit the panel once,
      revise the rows of 10% of its chunks, then refit — a full cold
      walk vs ``fit_chunked(delta_from=...)``, which adopts the 90% of
      chunks whose content fingerprints still match and recomputes only
      the dirty 10%.  ``delta_gate_ok`` requires the delta refit >= 3x
      faster than the full refit AND bitwise-identical to it.
    - **appended-ticks warm delta**: append a tick batch to every row
      (``write_npz_shards(append_time=...)``'s in-memory twin) and refit
      warm-started from the journaled params, with warm results pinned
      bitwise against a warm-started full walk.  Two numbers come out:
      ``warm_walk_speedup`` is the end-to-end journaled-walk ratio
      (commit/fingerprint overhead included — on a small host the shared
      durable-commit floor dilutes it), and ``warm_speedup`` is the FIT
      COMPUTE economy: summed per-chunk fit dispatch walls
      (``block_until_ready``, post-compile, best of 5 alternating grid
      passes), cold full-budget vs warm+probe-and-compact.
      ISSUE 20 floors ``warm_speedup`` at an absolute >= 2x on full
      runs: per-basin compaction must stop converged rows from riding
      full-budget lockstep dispatches, or the tick loop's per-cycle
      economy never pays.
    """
    import tempfile

    from spark_timeseries_tpu import reliability as rel
    from spark_timeseries_tpu.models import arima as _arima
    from spark_timeseries_tpu.reliability import delta as delta_mod

    if on_tpu and not quick:
        b, t_len, iters, n_chunks = 131_072, 1000, 60, 20
    elif quick:
        b, t_len, iters, n_chunks = 160, 120, 15, 20
    else:
        # sized so the per-chunk FIT dominates the walk (like any real
        # refit): the delta win is compute avoided, and a toy fit would
        # bench the journal's I/O instead
        b, t_len, iters, n_chunks = 2560, 512, 96, 20
    order = (1, 0, 1)
    chunk_rows = b // n_chunks
    y = gen_arima_panel(b, t_len, seed=45)
    root = tempfile.mkdtemp(prefix="deltans_")
    kw = dict(chunk_rows=chunk_rows, resilient=False, order=order,
              max_iters=iters)

    # the original fit: its v2 manifest carries the chunk fingerprints
    # every later delta diffs against (warm pass: compiles the program)
    rel.fit_chunked(_arima.fit, jnp.asarray(y),
                    checkpoint_dir=os.path.join(root, "full"), **kw)

    # -- leg 1: 10% of chunks revised -----------------------------------
    dirty_chunks = max(1, n_chunks // 10)
    y2 = np.array(y)
    y2[:dirty_chunks * chunk_rows] += np.float32(0.01)
    y2j = jnp.asarray(y2)
    t0 = time.perf_counter()
    ref = rel.fit_chunked(_arima.fit, y2j,
                          checkpoint_dir=os.path.join(root, "ref"), **kw)
    wall_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    d = rel.fit_chunked(_arima.fit, y2j,
                        checkpoint_dir=os.path.join(root, "delta"),
                        delta_from=os.path.join(root, "full"), **kw)
    wall_delta = time.perf_counter() - t0
    bitwise = all(
        np.array_equal(np.asarray(getattr(ref, f)),
                       np.asarray(getattr(d, f)), equal_nan=True)
        for f in ("params", "neg_log_likelihood", "converged", "iters",
                  "status"))
    counts = d.meta["delta"]["counts"]
    dirty_fraction = 1.0 - counts["adopted"] / max(1, sum(counts.values()))
    speedup = wall_full / wall_delta if wall_delta > 0 else None

    # -- leg 2: ticks appended to every row (warm-start refit) ----------
    # SMALL tick batches are the tick-loop regime: appended-optimum
    # drift grows with the batch, and by ~t_len/16 appended steps the
    # warm inits land outside the prior basin often enough that the
    # straggler refit stops paying (measured locally: 8 ticks -> warm
    # rows converge in ~2 iters; 32 ticks -> stragglers ride to 19+)
    ticks = 8
    # ... and BIG warm chunks are the compaction regime: the probe's
    # host sync amortizes over more rows, and each gathered straggler
    # sub-batch spares a wider lockstep from riding the full budget
    warm_rows = 512 if not (quick or on_tpu) else chunk_rows
    wkw = dict(kw, chunk_rows=warm_rows)
    y3 = np.concatenate(
        [np.array(y), gen_arima_panel(b, ticks, seed=46)
         + np.array(y)[:, -1:]], axis=1).astype(np.float32)
    y3j = jnp.asarray(y3)
    # the warm leg's prior journal, on the warm chunk grid (untimed)
    rel.fit_chunked(_arima.fit, jnp.asarray(y),
                    checkpoint_dir=os.path.join(root, "wfull"), **wkw)
    # the warm-started FULL walk the delta side verifies against (warm
    # starts change iteration counts, so the cold walk is not the
    # reference for this leg) — run FIRST, untimed: it also compiles
    # the warm programs (probe + straggler shape buckets), so both
    # timed walks below measure steady state, not XLA
    plan = rel.plan_delta(os.path.join(root, "wfull"), y3,
                          chunk_rows=warm_rows)
    wfit = delta_mod.WarmstartFit(_arima.fit, t_len + ticks, plan.k)
    wpanel = delta_mod.warm_panel(y3j, plan.init)
    wref = rel.fit_chunked(wfit, wpanel, align_mode="dense", **wkw)
    # ... and the cold program for the GROWN shape (t_len + ticks is a
    # new trace), so neither timed walk is charged XLA
    fit_kw = dict(order=order, max_iters=iters)
    _arima.fit(y3j[:warm_rows], **fit_kw).params.block_until_ready()
    # FIT COMPUTE economy — the floor-gated headline.  Journaled walks
    # share a durable-commit + fingerprint floor that a small host pays
    # on one core, so their ratio understates what the warm start
    # actually buys; this times the fit dispatches alone, blocked, over
    # the SAME chunk grid, steady-state.  Runs BEFORE the timed walks
    # (their journal writeback would steal the core from a later
    # measurement); best-of-5 alternating passes rides out scheduler
    # noise the way a single pair cannot
    def _grid_wall(fn, panel):
        t0 = time.perf_counter()
        for lo in range(0, b, warm_rows):
            fn(panel[lo:lo + warm_rows],
               **fit_kw).params.block_until_ready()
        return time.perf_counter() - t0

    cold_walls, warm_walls = [], []
    for _ in range(5):
        cold_walls.append(_grid_wall(_arima.fit, y3j))
        warm_walls.append(_grid_wall(wfit, wpanel))
    fit_wall_cold, fit_wall_warm = min(cold_walls), min(warm_walls)
    warm_speedup = (fit_wall_cold / fit_wall_warm
                    if fit_wall_warm > 0 else None)
    t0 = time.perf_counter()
    # full cold refit of the grown panel — JOURNALED like the delta side,
    # so the pair measures the warm start, not journal-I/O asymmetry
    rel.fit_chunked(_arima.fit, y3j,
                    checkpoint_dir=os.path.join(root, "grown_full"), **wkw)
    wall_grown_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    w = rel.fit_chunked(_arima.fit, y3j,
                        checkpoint_dir=os.path.join(root, "warm"),
                        delta_from=os.path.join(root, "wfull"), **wkw)
    wall_warm = time.perf_counter() - t0
    warm_bitwise = all(
        np.array_equal(np.asarray(getattr(wref, f)),
                       np.asarray(getattr(w, f)), equal_nan=True)
        for f in ("params", "neg_log_likelihood", "converged", "iters",
                  "status"))
    warm_walk_speedup = (wall_grown_full / wall_warm
                         if wall_warm > 0 else None)
    # quick (CI smoke) sizes are deliberately tiny, so the fixed plan/
    # adopt I/O dominates and the floors are meaningless there — quick
    # gates on the bitwise contracts; full runs gate both speedup floors
    # (ISSUE 20 raised the warm leg to an absolute >= 2x: per-basin
    # probe-and-compact must stop converged rows from riding full-budget
    # lockstep dispatches, or the tick-loop economy never pays)
    gate_ok = bool(bitwise and warm_bitwise
                   and (quick or (speedup is not None and speedup >= 3.0
                                  and warm_speedup is not None
                                  and warm_speedup >= 2.0)))
    import shutil

    shutil.rmtree(root, ignore_errors=True)
    return {
        "series_total": b,
        "obs_per_series": t_len,
        "chunks": n_chunks,
        "dirty_fraction": round(dirty_fraction, 4),
        "delta_counts": counts,
        "wall_s_full_refit": round(wall_full, 3),
        "wall_s_delta_refit": round(wall_delta, 3),
        "delta_speedup": round(speedup, 3) if speedup else None,
        "delta_bitwise_identical": bool(bitwise),
        "appended_ticks": ticks,
        "warm_chunk_rows": warm_rows,
        "warm_counts": w.meta["delta"]["counts"],
        "wall_s_grown_full_refit": round(wall_grown_full, 3),
        "wall_s_warm_delta": round(wall_warm, 3),
        "warm_walk_speedup": (round(warm_walk_speedup, 3)
                              if warm_walk_speedup else None),
        "fit_wall_s_cold": round(fit_wall_cold, 3),
        "fit_wall_s_warm": round(fit_wall_warm, 3),
        "warm_speedup": round(warm_speedup, 3) if warm_speedup else None,
        "warm_bitwise_vs_warm_reference": bool(warm_bitwise),
        "delta_gate_ok": gate_ok,
        "data": f"journaled delta refits of a {b} x {t_len} panel "
                f"({n_chunks} chunks): {dirty_chunks}-chunk revision "
                "adopts the rest byte-for-byte (floor: >=3x vs full "
                f"refit), then {ticks} appended ticks warm-start every "
                f"{warm_rows}-row chunk from the journaled params "
                "(floor: summed warm fit dispatches >=2x faster than "
                "cold full-budget over the same grid)",
    }


def _tick_loop_northstar(jnp, quick, on_tpu):
    """ISSUE 20 acceptance: the streaming loop — ticks in, forecasts out.

    Two legs, both journaled and both gated:

    - **sustained tick cycles**: a shard-dir panel runs K
      ``TickLoop.run_cycle`` batches end to end (record -> idempotent
      append -> delta-warm refit -> forecast -> write-back publish) and
      reports published forecast rows/sec across the whole run — every
      cycle must land ``published`` with finite forecasts, and cycles
      after the first must warm-chain off the previous cycle's journal
      (zero adopted, all warm: appended ticks dirty every chunk's tail).
    - **delta-adopting campaign** (the floor-gated headline): a
      10-window backtest campaign at width T, then the SAME campaign
      plus one appended-origin window on the grown panel run twice —
      ``delta=True`` against the prior campaign's manifest vs a fresh
      recompute in a clean directory.  The adopted windows do zero fit
      compute, every window's digest must match the fresh campaign's
      exactly, and ``tick_loop_gate_ok`` floors the campaign speedup at
      >= 2x on full runs.
    """
    import shutil
    import tempfile

    from spark_timeseries_tpu.forecasting import backtest as bt_mod
    from spark_timeseries_tpu.reliability import source as source_mod
    from spark_timeseries_tpu.serving import tickloop as tl_mod

    if on_tpu and not quick:
        b, t0, iters, chunk_rows = 65_536, 512, 60, 8192
        cycles, n_ticks, n_windows = 3, 8, 10
    elif quick:
        b, t0, iters, chunk_rows = 64, 96, 15, 16
        cycles, n_ticks, n_windows = 2, 4, 3
    else:
        b, t0, iters, chunk_rows = 256, 256, 48, 32
        cycles, n_ticks, n_windows = 3, 8, 10
    horizon = 8
    order = (1, 0, 1)
    y = gen_arima_panel(b, t0 + cycles * n_ticks, seed=47)
    root = tempfile.mkdtemp(prefix="tickns_")

    # -- leg 1: K tick-to-publish cycles --------------------------------
    data = os.path.join(root, "data")
    source_mod.write_npz_shards(data, y[:, :t0], chunk_rows)
    loop = tl_mod.TickLoop(
        os.path.join(root, "loop"), data, model="arima",
        model_kwargs={"order": order}, fit_kwargs={"max_iters": iters},
        horizon=horizon, chunk_rows=chunk_rows, seed=48)
    t_start = time.perf_counter()
    results = [loop.run_cycle(y[:, t0 + c * n_ticks:
                                t0 + (c + 1) * n_ticks])
               for c in range(cycles)]
    wall_cycles = time.perf_counter() - t_start
    published = all(r.meta["stage"] == "published" for r in results)
    point, _, _ = loop.published_forecast()
    # the never-garbage contract, not all-finite: rows whose fit was
    # unusable forecast NaN BY DESIGN, so the gate is "NaN exactly where
    # the published status counts say the fit failed"
    sc = results[-1].meta["published"]["status_counts"]
    n_bad = sum(int(v) for k, v in sc.items()
                if str(k) in ("DIVERGED", "EXCLUDED", "TIMEOUT"))
    n_nan = int((~np.isfinite(np.asarray(point)).all(axis=1)).sum())
    finite = bool(n_nan == n_bad)
    # the steady state of a tick feed: nothing adopted (appended ticks
    # dirty every chunk's tail), everything warm off the previous cycle
    warm_chained = all(
        r.meta.get("delta_counts", {}).get("adopted", -1) == 0
        and r.meta.get("delta_counts", {}).get("dirty", -1) == 0
        for r in results[1:])
    rows_per_sec = b * cycles / wall_cycles if wall_cycles > 0 else None
    cycle_walls = [sum(r.meta["walls"].values()) for r in results]

    # -- leg 2: delta-adopting backtest campaign ------------------------
    bt_kw = dict(model_kwargs={"order": order},
                 fit_kwargs={"max_iters": iters}, chunk_rows=chunk_rows,
                 warm_start=True)
    origins = bt_mod.default_origins(t0, horizon, n_windows)
    bt_mod.run_backtest(y[:, :t0], "arima", horizon, origins=origins,
                        checkpoint_dir=os.path.join(root, "bt"), **bt_kw)
    grown = y[:, :t0 + n_ticks]
    # the appended window scores against the last `horizon` actuals the
    # grown panel can hold — strictly past the prior campaign's last
    # origin, so it is the one window adoption cannot cover
    origins2 = origins + [t0 + n_ticks - horizon]
    # fresh FIRST: the appended window's compile lands on the fresh
    # campaign, so the delta side measures adoption, not a cold cache
    t_f = time.perf_counter()
    fres = bt_mod.run_backtest(grown, "arima", horizon, origins=origins2,
                               checkpoint_dir=os.path.join(root, "fresh"),
                               **bt_kw)
    wall_fresh_bt = time.perf_counter() - t_f
    t_d = time.perf_counter()
    dres = bt_mod.run_backtest(grown, "arima", horizon, origins=origins2,
                               checkpoint_dir=os.path.join(root, "bt"),
                               delta=True, **bt_kw)
    wall_delta_bt = time.perf_counter() - t_d
    bt_bitwise = (len(dres.windows) == len(fres.windows) and all(
        dw["digest"] == fw["digest"]
        for dw, fw in zip(dres.windows, fres.windows)))
    adopted = int(dres.meta.get("delta", {}).get("adopted", 0))
    bt_speedup = (wall_fresh_bt / wall_delta_bt
                  if wall_delta_bt > 0 else None)
    # quick sizes are tiny enough that campaign setup I/O dominates —
    # quick gates the contracts; full runs also floor the adoption win
    gate_ok = bool(published and finite and warm_chained and bt_bitwise
                   and adopted == len(origins)
                   and (quick or (bt_speedup is not None
                                  and bt_speedup >= 2.0)))
    shutil.rmtree(root, ignore_errors=True)
    return {
        "series_total": b,
        "cycles": cycles,
        "ticks_per_cycle": n_ticks,
        "wall_s_cycles": round(wall_cycles, 3),
        "cycle_wall_s_mean": round(float(np.mean(cycle_walls)), 3),
        "published_rows_per_sec": (round(rows_per_sec, 1)
                                   if rows_per_sec else None),
        "all_cycles_published": bool(published),
        "published_finite": finite,
        "warm_chained": bool(warm_chained),
        "backtest_windows": len(origins2),
        "backtest_adopted": adopted,
        "wall_s_delta_backtest": round(wall_delta_bt, 3),
        "wall_s_fresh_backtest": round(wall_fresh_bt, 3),
        "backtest_delta_speedup": (round(bt_speedup, 3)
                                   if bt_speedup else None),
        "backtest_bitwise_identical": bool(bt_bitwise),
        "tick_loop_gate_ok": gate_ok,
        "data": f"{cycles} tick cycles of {n_ticks} ticks on a {b} x "
                f"{t0} shard-dir panel (append -> delta-warm refit -> "
                f"forecast -> write-back publish), then a "
                f"{len(origins)}-window campaign adopted onto the grown "
                f"panel vs a fresh recompute (floor: >=2x, digests "
                "identical)",
    }


def _warm_tenant_northstar(jnp, quick, on_tpu):
    """ISSUE 19 acceptance: warm per-tenant auto-fit — the fleet gets
    cheaper per tenant the longer it runs.

    N tenants make K identical auto-fit passes through a resident
    :class:`serving.FitServer`.  Pass 1 is the cold story (route
    ``new``: the full stepwise Hyndman–Khandakar search); every later
    identical submit classifies **stable** against the tenant's durable
    profile and warm-refits the known per-row winners, skipping stage 1
    entirely.  Reported: per-pass aggregate walls, the
    ``warm_tenant_speedup`` (pass-1 wall / pass-K wall; floor-gated at
    >= 2x on full local runs — quick CI sizes are fixed-overhead-
    dominated and gate only the routing/selection contracts), the
    route ladder each tenant walked, the warm pass's EXACT selection
    agreement with pass 1 (the stable leg refits the profile's winner
    map — any drift is a routing bug), and the informational agreement
    between the stepwise selection and a cold exact-mode
    (``warm_routing=False``) exhaustive submit whose default grid the
    stepwise search does not share.  The server and its profile store
    are compile-warmed by a scratch tenant's cold+warm passes, so the
    measured walls are steady-state serving.
    """
    import shutil
    import tempfile

    from spark_timeseries_tpu import serving
    from spark_timeseries_tpu.serving.server import AUTO_MODEL

    if on_tpu and not quick:
        n_tenants, rows, t_len, iters, passes = 4, 8192, 1000, 60, 3
    elif quick:
        n_tenants, rows, t_len, iters, passes = 2, 8, 96, 20, 2
    else:
        n_tenants, rows, t_len, iters, passes = 3, 24, 160, 30, 3
    fk = dict(max_iters=iters, stepwise_max_passes=3, stepwise_max_order=2)
    tenants = [f"tenant-{i}" for i in range(n_tenants)]
    panels = {tn: gen_arima_panel(rows, t_len, seed=70 + i)
              for i, tn in enumerate(tenants)}

    root = tempfile.mkdtemp(prefix="warmns_")
    pass_walls = []
    metas = {tn: [] for tn in tenants}
    with serving.FitServer(root, cell_rows=rows) as srv:
        # warm-up: a scratch tenant's cold pass compiles the stepwise
        # search programs, its second (stable) pass compiles the
        # per-basin warm-refit programs — both outside the timed walls
        wy = gen_arima_panel(rows, t_len, seed=69)
        for _ in range(2):
            srv.submit("warmup", wy, model=AUTO_MODEL,
                       **fk).result(timeout=1800)
        for _p in range(passes):
            t0 = time.perf_counter()
            for tn in tenants:
                res = srv.submit(tn, panels[tn], model=AUTO_MODEL,
                                 **fk).result(timeout=1800)
                metas[tn].append(res.meta["auto"])
            pass_walls.append(time.perf_counter() - t0)
        counters = srv.health()["counters"]
        # the exact-mode fallback leg: warm_routing=False bypasses the
        # profile entirely — a plain exhaustive search over the default
        # grid (its bitwise contract vs direct auto_fit is tier-1; here
        # it provides the selection-agreement reference)
        cold = srv.submit(tenants[0], panels[tenants[0]], model=AUTO_MODEL,
                          max_iters=iters,
                          warm_routing=False).result(timeout=1800)
    shutil.rmtree(root, ignore_errors=True)

    routes = {tn: [m["route"] for m in ms] for tn, ms in metas.items()}
    routes_ok = all(r == ["new"] + ["stable"] * (passes - 1)
                    for r in routes.values())
    # the stable leg must reproduce pass 1's selection EXACTLY: it
    # refits the profile's winner map, it does not search
    sel_exact = all(ms[p]["order_index"] == ms[0]["order_index"]
                    for ms in metas.values() for p in range(1, passes))

    def _winner_tuples(meta):
        orders = np.asarray(meta["orders"], np.int64)
        idx = np.asarray(meta["order_index"], np.int64)
        out = np.full((idx.shape[0], 3), -1, np.int64)
        out[idx >= 0] = orders[idx[idx >= 0]]
        return out

    exh_agree = float(np.mean(np.all(
        _winner_tuples(metas[tenants[0]][-1])
        == _winner_tuples(cold.meta["auto"]), axis=1)))
    speedup = (pass_walls[0] / pass_walls[-1]
               if pass_walls[-1] > 0 else None)
    # quick sizes are fixed-overhead-dominated (journal I/O, dispatch)
    # and gate only the contracts; full runs gate the 2x floor —
    # pass-K at <= 0.5x the pass-1 wall is the tentpole's promise
    gate_ok = bool(routes_ok and sel_exact
                   and (quick or (speedup is not None and speedup >= 2.0)))
    return {
        "tenants": n_tenants,
        "rows_per_tenant": rows,
        "obs_per_series": t_len,
        "passes": passes,
        "pass_walls_s": [round(w, 3) for w in pass_walls],
        "wall_s_cold_pass": round(pass_walls[0], 3),
        "wall_s_warm_pass": round(pass_walls[-1], 3),
        "warm_tenant_speedup": (round(speedup, 3)
                                if speedup is not None else None),
        "routes": routes[tenants[0]],
        "routes_ok": routes_ok,
        "warm_selection_exact": sel_exact,
        "exhaustive_agreement": round(exh_agree, 4),
        "route_counters": {k: v for k, v in sorted(counters.items())
                           if k.startswith(("route_", "profile_"))},
        "warm_tenant_gate_ok": gate_ok,
        "data": f"{n_tenants} tenants x {passes} identical auto-fit "
                f"passes ({rows} rows x {t_len} obs each) through a "
                "resident FitServer: pass 1 runs the journaled stepwise "
                "search, later passes route stable off the durable "
                "tenant profile and warm-refit the known winners "
                "(floor: warm pass <= 0.5x the cold pass on full runs)",
    }


def bench_arima_headline(jnp, quick, on_tpu, n_chips, platform, parity=None):
    from spark_timeseries_tpu.models import arima

    b = 1024 if quick else (100_352 if on_tpu else 256)  # 98 x 1024 blocks
    t = 200 if quick else 1000
    order = (1, 1, 1)
    panels = [gen_arima_panel(b, t, seed=s) for s in range(4 if on_tpu else 2)]
    dev = stage(jnp, panels)

    state = {}

    def run(v):
        r = arima.fit(v, order)  # library-default budget (60 iters) + tol
        state["conv"] = float(jnp.mean(r.converged))
        state["res"] = r
        return float(jnp.sum(jnp.nan_to_num(r.params)))

    times = time_calls(run, dev)
    best = min(times)
    p50 = float(np.median(times))
    frac_conv = state["conv"]
    rate = b / best
    rate_converged = b * frac_conv / best

    # forecast ride-along (config says fit + forecast): since ISSUE 14
    # this measures the REAL serving surface — the chunked panel forecast
    # walk (forecasting.forecast_chunked) — not a bare kernel call; warm
    # the compile first so the latency reflects execution, not tracing
    # (VERDICT round 2)
    from spark_timeseries_tpu import forecasting as fcast

    r = state["res"]
    fc = fcast.forecast_chunked("arima", r, dev[-1], 10,
                                model_kwargs={"order": order})
    t0 = time.perf_counter()
    fc = fcast.forecast_chunked(  # params fit ON dev[-1]
        "arima", r, dev[-1], 10, model_kwargs={"order": order})
    float(np.nansum(fc.forecast))
    forecast_s = time.perf_counter() - t0
    # config 3 is specified as fit + forecast (BASELINE.md): the combined
    # rate is the honest headline denominator (VERDICT r3 item 1)
    combined_rate = b * frac_conv / (best + forecast_s)

    # pass accounting (VERDICT r4 item 2): one instrumented fit of the
    # headline program — published so "how many objective passes does a fit
    # spend" is a recorded number, not a latency-division estimate
    acct = {}
    # reliability accounting (ISSUE 1): per-row FitStatus totals of the
    # timed fit — how many rows were OK vs DIVERGED/EXCLUDED, so "converged
    # fraction" has a per-row breakdown in the artifact
    if state["res"].status is not None:
        from spark_timeseries_tpu.reliability import status_counts

        acct["fit_status_counts"] = status_counts(state["res"].status)
    if on_tpu:
        r_i, info = arima.fit(dev[0], order, count_evals=True)
        acct = {**acct, **_pass_accounting(info, r_i.iters, b, t, best)}
    if on_tpu and not quick:
        _progress("config 3: north-star 1M x 1k sustained run...")
        acct["northstar_1m"] = _northstar_1m(jnp, order)
    # ISSUE 6: the same workload as ONE mesh-wide journaled job — runs on
    # any >=2 local devices (real chips or forced virtual CPU devices), at
    # full 1M x 1k size on TPU non-quick runs
    _progress("config 3: sharded north-star (mesh-wide journaled walk)...")
    acct["sharded_northstar"] = _sharded_northstar(jnp, order, quick, on_tpu)
    # ISSUE 7: the same workload with the panel NEVER fully resident on
    # device — a journaled host-resident walk vs the in-HBM ceiling
    _progress("config 3: oversubscribed north-star (host-resident walk)...")
    acct["oversubscribed_northstar"] = _oversubscribed_northstar(
        jnp, order, quick, on_tpu)
    # ISSUE 9: auto model selection — a grid of candidate orders per
    # series as one journaled search (candidate-orders x series/sec)
    _progress("config 3: auto-fit north-star (batched order search)...")
    acct["auto_fit_northstar"] = _auto_fit_northstar(jnp, quick, on_tpu)
    # ISSUE 12: the resident serving loop — multi-tenant request storm
    # throughput/latency, batching amplification, 2x-overload shedding
    _progress("config 3: serving north-star (resident fit server)...")
    acct["serving_northstar"] = _serving_northstar(jnp, quick, on_tpu)
    # ISSUE 16: the fleet behind a socket — through-the-wire storm
    # throughput/latency + the failover-recovery price of a primary
    # crash under the lease/fencing protocol
    _progress("config 3: fleet north-star (lease-fenced replicas)...")
    acct["fleet_serving_northstar"] = _fleet_serving_northstar(
        jnp, quick, on_tpu)
    # ISSUE 17: graceful degradation — read availability through a
    # primary kill (standby reads cover the leaderless window) and
    # degraded-read throughput off a lease-less standby
    _progress("config 3: chaos north-star (degradation ladder)...")
    acct["chaos_northstar"] = _chaos_northstar(jnp, quick, on_tpu)
    # ISSUE 14: the panel forecast surface — journaled forecast walk
    # rows/sec, resume/from-journal bitwise, backtest campaign wall,
    # ensemble overhead
    _progress("config 3: forecast north-star (journaled forecast walk)...")
    acct["forecast_northstar"] = _forecast_northstar(jnp, quick, on_tpu)
    # ISSUE 15: tick-to-fit — a 10%-dirty panel revision refit as a delta
    # walk (adopt clean chunks, recompute dirty) vs the full refit, plus
    # the appended-ticks warm-start leg
    _progress("config 3: delta-refit north-star (incremental refit)...")
    acct["delta_refit_northstar"] = _delta_refit_northstar(jnp, quick,
                                                           on_tpu)
    # ISSUE 20: tick-to-forecast streaming — K TickLoop cycles (append ->
    # delta-warm refit -> forecast -> write-back publish) plus the
    # delta-adopting backtest campaign vs a fresh recompute
    _progress("config 3: tick-loop north-star (streaming cycles)...")
    acct["tick_loop_northstar"] = _tick_loop_northstar(jnp, quick, on_tpu)
    # ISSUE 19: warm per-tenant auto-fit — durable profiles route repeat
    # submits to warm winner refits; pass-K must undercut pass-1
    _progress("config 3: warm-tenant north-star (profile-routed "
              "auto-fit)...")
    acct["warm_tenant_northstar"] = _warm_tenant_northstar(jnp, quick,
                                                           on_tpu)

    cpu_rate, n_done = cpu_rate_arima(t, 2.0 if quick else CPU_BUDGET_S)
    n_cores = os.cpu_count() or 1
    target = NORTH_STAR * n_chips / 8.0
    return {
        "metric": (
            f"config3 HEADLINE: ARIMA(1,1,1) CSS-MLE fit throughput ({t} obs/series, "
            f"batch {b}, {n_chips}x {platform}, converged {frac_conv:.3f})"
        ),
        "value": round(rate_converged, 1),
        "unit": "series/sec (converged-only; raw rate x converged fraction)",
        "vs_baseline": round(rate_converged / target, 4),
        "raw_series_per_sec": round(rate, 1),
        "converged_frac": round(frac_conv, 4),
        "vs_target_unscaled": round(rate_converged / NORTH_STAR, 4),
        "p50_fit_latency_s": round(p50, 3),
        "best_fit_latency_s": round(best, 3),
        "forecast_latency_s": round(forecast_s, 3),
        "fit_plus_forecast_series_per_sec": round(combined_rate, 1),
        "fit_plus_forecast_vs_target_unscaled": round(combined_rate / NORTH_STAR, 4),
        "cpu_series_per_sec_1core": round(cpu_rate, 2),
        "cpu_series_per_sec_allcore_est": round(cpu_rate * n_cores, 1),
        "cpu_oracle_series_measured": n_done,
        "speedup_vs_cpu_1core": round(rate_converged / cpu_rate, 1),
        "speedup_vs_cpu_allcore": round(rate_converged / (cpu_rate * n_cores), 2),
        **acct,
        # the gate line prints FIRST and the driver keeps only the output
        # tail, so the verdict must ride the headline to survive truncation
        "parity_gate": parity if parity is not None else {"checked": False},
    }


def _telemetry_regression_gate(headline):
    """Diff this run's telemetry summary against the previous local run.

    ROADMAP satellite: the throughput headline can stay flat while the
    numbers under it rot — compile-time share creeping up (a new trace in
    the hot path), journal commit latency growing (fsync regression,
    bigger shards), the map_series kernel cache suddenly missing, or the
    pipelined commit overlap collapsing back to serial.  This gate reads
    the PREVIOUS ``BENCH_LOCAL.json`` tail (where the prior run's
    ``telemetry_summary`` line survives verbatim), compares the tracked
    metrics (compile share, commit latency, map_series cache rate, and
    both overlap efficiencies — commit-side and input-staging), and flags
    drifts beyond tolerance.  Fail-soft by
    design: a missing prior summary reports ``checked: false`` rather
    than failing the benchmark.

    Returns ``(telemetry_summary_line, gate_line)`` — both are emitted so
    the NEXT run finds this run's summary in its own tail.
    """
    inputs = (headline.get("northstar_1m") or {}).get("telemetry_gate_inputs")
    # sharded-walk gate inputs (ISSUE 6) ride the same summary line: the
    # mesh speedup and the worst lane's commit overlap are exactly the
    # numbers that can rot while the single-device headline stays flat
    sh = headline.get("sharded_northstar") or {}
    if not sh.get("skipped") and sh.get("sharded_speedup") is not None:
        inputs = {
            **(inputs or {}),
            "sharded_speedup": sh.get("sharded_speedup"),
            "shard_overlap_efficiency_min":
                sh.get("shard_overlap_efficiency_min"),
            # ISSUE 11: the elastic walk's degraded-mode numbers — losing
            # a lane must keep beating the single device, and the
            # quarantine/rebalance machinery must stay cheap
            "degraded_speedup": sh.get("degraded_speedup"),
            "rebalance_overhead": sh.get("rebalance_overhead"),
        }
    # host-resident-walk gate inputs (ISSUE 7): the H2D overlap can rot
    # (prefetcher regression, staging pool thrash) while the in-HBM
    # headline stays flat — the throughput ratio is the canary
    ov = headline.get("oversubscribed_northstar") or {}
    if ov.get("host_over_hbm_throughput") is not None:
        inputs = {
            **(inputs or {}),
            "oversubscribed_ratio": ov.get("host_over_hbm_throughput"),
        }
    # auto-fit gate inputs (ISSUE 9): the order-search throughput, the
    # per-order program-reuse rate, and the winners-economy agreement —
    # a compile-cache keying regression or a selection drift would hide
    # behind a flat single-fit headline
    af = headline.get("auto_fit_northstar") or {}
    if af.get("order_series_per_sec") is not None:
        inputs = {
            **(inputs or {}),
            "auto_fit_order_series_per_sec": af.get("order_series_per_sec"),
            "auto_fit_compile_cache_hit_rate":
                af.get("compile_cache_hit_rate"),
            "auto_fit_stage2_spend_share":
                af.get("winners_stage2_spend_share"),
            "auto_fit_winners_agreement":
                af.get("winners_selection_agreement"),
            # ISSUE 10: the fusion win, the shared-prep savings, and the
            # repaired winners economy — each can silently rot (a fused
            # group falling back to per-order walks, a diff-cache keying
            # regression, the economy sliding back below 1x)
            "auto_fit_fused_speedup": af.get("fused_speedup"),
            "auto_fit_diff_cache_hits": af.get("diff_cache_hits"),
            "auto_fit_winners_speedup": af.get("winners_speedup"),
        }
    # serving gate inputs (ISSUE 12): sustained throughput, tail latency,
    # the batching win, and the overload contract — a serving regression
    # (coalescing silently off, shedding broken) hides behind every
    # one-shot headline
    sv = headline.get("serving_northstar") or {}
    if sv.get("rows_per_sec") is not None:
        inputs = {
            **(inputs or {}),
            "serving_rows_per_sec": sv.get("rows_per_sec"),
            "serving_p99_latency_s": sv.get("p99_request_latency_s"),
            "serving_batch_amplification": sv.get("batch_amplification"),
            "serving_gate_ok": 1.0 if sv.get("serving_gate_ok") else 0.0,
        }
    # fleet gate inputs (ISSUE 16): through-the-wire throughput, the
    # failover price, and the takeover contract — a fleet regression
    # (fencing broken, takeover re-answers drifting) hides behind the
    # in-process serving numbers
    fl = headline.get("fleet_serving_northstar") or {}
    if fl.get("rows_per_sec") is not None:
        inputs = {
            **(inputs or {}),
            "fleet_rows_per_sec": fl.get("rows_per_sec"),
            "fleet_failover_wall_s": fl.get("failover_request_wall_s"),
            "fleet_gate_ok": 1.0 if fl.get("fleet_gate_ok") else 0.0,
            # ISSUE 18: the traced/untraced storm-throughput ratio — the
            # price of fleet-wide tracing, drift- and floor-gated
            "fleet_obs_overhead_ratio": fl.get("obs_overhead_ratio"),
        }
    # chaos gate inputs (ISSUE 17): the availability contract — probe ok
    # rate through a primary kill, degraded-read throughput off a
    # standby, and the composed gate — a degradation-ladder regression
    # (standby reads silently off, refusal broken) hides behind every
    # happy-path fleet number
    ch = headline.get("chaos_northstar") or {}
    if ch.get("probe_ok_rate") is not None:
        inputs = {
            **(inputs or {}),
            "chaos_probe_ok_rate": ch.get("probe_ok_rate"),
            "chaos_degraded_reads_per_sec":
                ch.get("degraded_reads_per_sec"),
            "chaos_gate_ok": 1.0 if ch.get("chaos_gate_ok") else 0.0,
        }
    # forecast gate inputs (ISSUE 14): panel forecast throughput and the
    # composed bitwise contracts — a forecast-walk regression (resume
    # splicing, ensemble drift) hides behind every fit-side headline
    fo = headline.get("forecast_northstar") or {}
    if fo.get("forecast_rows_per_sec") is not None:
        inputs = {
            **(inputs or {}),
            "forecast_rows_per_sec": fo.get("forecast_rows_per_sec"),
            "forecast_gate_ok": 1.0 if fo.get("forecast_gate_ok") else 0.0,
        }
    # delta-refit gate inputs (ISSUE 15): the incremental-refit win and
    # its bitwise contract — a planner regression (adoption silently off,
    # fingerprints churning) degenerates every delta to a full refit
    # while the cold headline stays flat
    de = headline.get("delta_refit_northstar") or {}
    if de.get("delta_speedup") is not None:
        inputs = {
            **(inputs or {}),
            "delta_speedup": de.get("delta_speedup"),
            "delta_warm_speedup": de.get("warm_speedup"),
            "delta_gate_ok": 1.0 if de.get("delta_gate_ok") else 0.0,
        }
    # tick-loop gate inputs (ISSUE 20): the streaming economy — published
    # rows/sec across cycles and the campaign-adoption win; a planner or
    # sink regression (cycles recomputing cold, adoption silently off)
    # hides behind every single-walk headline
    tk = headline.get("tick_loop_northstar") or {}
    if tk.get("published_rows_per_sec") is not None:
        inputs = {
            **(inputs or {}),
            "tick_loop_rows_per_sec": tk.get("published_rows_per_sec"),
            "tick_backtest_speedup": tk.get("backtest_delta_speedup"),
            "tick_loop_gate_ok": 1.0 if tk.get("tick_loop_gate_ok")
                                 else 0.0,
        }
    # warm-tenant gate inputs (ISSUE 19): the profile-routing win and
    # its selection contract — a classifier regression (every pass
    # re-searching cold, or the warm refit drifting off the profile's
    # winner map) hides behind every single-search headline
    wt = headline.get("warm_tenant_northstar") or {}
    if wt.get("warm_tenant_speedup") is not None:
        inputs = {
            **(inputs or {}),
            "warm_tenant_speedup": wt.get("warm_tenant_speedup"),
            "warm_tenant_gate_ok":
                1.0 if wt.get("warm_tenant_gate_ok") else 0.0,
        }
    cur = {
        "metric": "telemetry_summary: regression-gate inputs "
                  "(compile share, commit latency, map_series cache, "
                  "overlap; diffed by the next run)",
        "value": 1.0 if inputs else 0.0,
        "unit": "available",
        **(inputs or {}),
    }
    prev = None
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_LOCAL.json")
        with open(path) as f:
            tail = json.load(f).get("tail", "")
        for line in tail.splitlines():
            line = line.strip()
            if line.startswith("{") and '"telemetry_summary' in line:
                try:
                    prev = json.loads(line)  # keep the LAST one in the tail
                except json.JSONDecodeError:
                    continue
    except (OSError, json.JSONDecodeError, AttributeError):
        prev = None
    gate = {
        "metric": "telemetry_regression_gate: drift vs previous "
                  "BENCH_LOCAL.json telemetry (what the throughput "
                  "headline hides)",
        "value": None,
        "unit": "ok",
        "checked": False,
        "ok": None,
        "drifts": {},
    }
    if not inputs or prev is None:
        gate["reason"] = ("no telemetry inputs this run (north-star not "
                          "executed or obs unavailable)" if not inputs
                          else "no previous telemetry_summary in "
                               "BENCH_LOCAL.json")
        return cur, gate
    # shares/rates in [0, 1] gate on ABSOLUTE drift; latency on RELATIVE.
    # Each metric carries a DIRECTION (ISSUE 10 satellite: the gate once
    # flagged sharded_speedup 1.93 -> 2.88 — an improvement — as drift):
    # "higher" metrics flag only when they DROP past tolerance, "lower"
    # only when they RISE, "both" keeps the two-sided band.  The recorded
    # drift stays signed-magnitude so improvements remain visible.
    thresholds = {
        "compile_time_share": ("abs", 0.15, "lower"),
        "journal_commit_s_mean": ("rel", 0.5, "lower"),
        "map_series_cache_hit_rate": ("abs", 0.15, "higher"),
        "overlap_efficiency": ("abs", 0.15, "higher"),
        "input_overlap_efficiency": ("abs", 0.15, "higher"),
        "sharded_speedup": ("rel", 0.3, "higher"),
        "shard_overlap_efficiency_min": ("abs", 0.2, "higher"),
        "degraded_speedup": ("rel", 0.4, "higher"),
        # absolute drift: the overhead hovers near 0 (and can be negative)
        # where a relative band is all timing noise
        "rebalance_overhead": ("abs", 0.5, "lower"),
        "oversubscribed_ratio": ("abs", 0.2, "higher"),
        "auto_fit_order_series_per_sec": ("rel", 0.4, "higher"),
        "auto_fit_compile_cache_hit_rate": ("abs", 0.2, "higher"),
        "auto_fit_stage2_spend_share": ("abs", 0.25, "both"),
        "auto_fit_winners_agreement": ("abs", 0.1, "higher"),
        "auto_fit_fused_speedup": ("rel", 0.4, "higher"),
        "auto_fit_diff_cache_hits": ("rel", 0.5, "higher"),
        "auto_fit_winners_speedup": ("rel", 0.5, "higher"),
        "serving_rows_per_sec": ("rel", 0.5, "higher"),
        "serving_p99_latency_s": ("rel", 1.0, "lower"),
        "serving_batch_amplification": ("rel", 0.4, "higher"),
        "chaos_probe_ok_rate": ("abs", 0.1, "higher"),
        "chaos_degraded_reads_per_sec": ("rel", 0.5, "higher"),
        "fleet_obs_overhead_ratio": ("abs", 0.3, "higher"),
        "forecast_rows_per_sec": ("rel", 0.5, "higher"),
        "delta_speedup": ("rel", 0.4, "higher"),
        "delta_warm_speedup": ("rel", 0.5, "higher"),
        "warm_tenant_speedup": ("rel", 0.5, "higher"),
        "tick_loop_rows_per_sec": ("rel", 0.5, "higher"),
        "tick_backtest_speedup": ("rel", 0.5, "higher"),
    }
    drifts, flagged = {}, []
    for k, (mode, tol, direction) in thresholds.items():
        a, b = prev.get(k), inputs.get(k)
        if a is None or b is None:
            continue
        signed = (b - a) if mode == "abs" else (b - a) / max(abs(a), 1e-9)
        delta = abs(signed)
        if direction == "higher":
            bad = -signed > tol  # only a DROP is a regression
        elif direction == "lower":
            bad = signed > tol  # only a RISE is a regression
        else:
            bad = delta > tol
        drifts[k] = {"prev": a, "cur": b, "drift": round(delta, 4),
                     "tolerance": tol, "mode": mode,
                     "direction": direction, "flagged": bad}
        if bad:
            flagged.append(k)
    # ABSOLUTE floor (ISSUE 10): the winners economy must BE an economy —
    # PR 8 shipped it 18x slower and the previous-run drift comparison
    # alone would bless a slow-but-stable regression forever
    ws = inputs.get("auto_fit_winners_speedup")
    if ws is not None and ws < 1.0:
        drifts["auto_fit_winners_speedup_floor"] = {
            "prev": 1.0, "cur": ws, "drift": round(1.0 - ws, 4),
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("auto_fit_winners_speedup_floor")
    # ABSOLUTE floor (ISSUE 11): losing 1 of n lanes must DEGRADE the mesh
    # win, never erase it — a degraded walk slower than the single device
    # means quarantine/rebalance is broken, regardless of the previous run
    ds = inputs.get("degraded_speedup")
    if ds is not None and ds < 1.0:
        drifts["degraded_speedup_floor"] = {
            "prev": 1.0, "cur": ds, "drift": round(1.0 - ds, 4),
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("degraded_speedup_floor")
    # ABSOLUTE floor (ISSUE 12): overload must degrade to explicit
    # shedding with conservation — a server that OOMs, hangs, or loses
    # requests under 2x load is broken regardless of the previous run
    sg = inputs.get("serving_gate_ok")
    if sg is not None and sg < 1.0:
        drifts["serving_overload_floor"] = {
            "prev": 1.0, "cur": sg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("serving_overload_floor")
    # ABSOLUTE floor (ISSUE 16): a failover must re-answer the in-flight
    # request bitwise with the lease on the survivor — a fleet that
    # loses a request or splices stale bytes across a takeover is broken
    # regardless of the previous run
    flg = inputs.get("fleet_gate_ok")
    if flg is not None and flg < 1.0:
        drifts["fleet_failover_floor"] = {
            "prev": 1.0, "cur": flg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("fleet_failover_floor")
    # ABSOLUTE floor (ISSUE 18): observability must stay cheap — a
    # traced storm running at less than half the untraced throughput
    # means the trace/recorder path regressed into the hot loop,
    # regardless of the previous run
    oor = inputs.get("fleet_obs_overhead_ratio")
    if oor is not None and oor < 0.5:
        drifts["fleet_obs_overhead_floor"] = {
            "prev": 0.5, "cur": oor, "drift": round(0.5 - oor, 4),
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("fleet_obs_overhead_floor")
    # ABSOLUTE floor (ISSUE 17): degradation is the contract — standby
    # reads must hold availability through a primary kill, the standby
    # must serve durable bytes bitwise and refuse writes; a fleet that
    # goes dark in the leaderless window is broken regardless of the
    # previous run
    cg = inputs.get("chaos_gate_ok")
    if cg is not None and cg < 1.0:
        drifts["chaos_availability_floor"] = {
            "prev": 1.0, "cur": cg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("chaos_availability_floor")
    # ABSOLUTE floor (ISSUE 14): the composed forecast contracts — resume
    # bitwise, from-journal bitwise, ensemble argmin/weights, the
    # campaign completing — are correctness, not perf: any miss is broken
    # regardless of the previous run
    fg = inputs.get("forecast_gate_ok")
    if fg is not None and fg < 1.0:
        drifts["forecast_bitwise_floor"] = {
            "prev": 1.0, "cur": fg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("forecast_bitwise_floor")
    # ABSOLUTE floor (ISSUE 15): a 10%-dirty delta must beat the full
    # refit by >= 3x AND stay bitwise — anything less means adoption is
    # broken or splicing wrong bytes, regardless of the previous run
    dg = inputs.get("delta_gate_ok")
    if dg is not None and dg < 1.0:
        drifts["delta_refit_floor"] = {
            "prev": 1.0, "cur": dg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("delta_refit_floor")
    # ABSOLUTE floor (ISSUE 20): the streaming loop is the contract —
    # every cycle published with finite forecasts warm-chained off the
    # previous journal, and a delta campaign adopting its prior's windows
    # digest-identical at >= 2x; a loop that recomputes cold or splices
    # wrong window bytes is broken regardless of the previous run
    tg = inputs.get("tick_loop_gate_ok")
    if tg is not None and tg < 1.0:
        drifts["tick_loop_floor"] = {
            "prev": 1.0, "cur": tg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("tick_loop_floor")
    # ABSOLUTE floor (ISSUE 19): warm routing is the contract — repeat
    # submits must classify stable and the warm refit must reproduce the
    # profile's winner map exactly (and undercut the cold pass 2x on
    # full runs); a classifier or profile regression that re-searches
    # every pass is broken regardless of the previous run
    wg = inputs.get("warm_tenant_gate_ok")
    if wg is not None and wg < 1.0:
        drifts["warm_tenant_floor"] = {
            "prev": 1.0, "cur": wg, "drift": 1.0,
            "tolerance": 0.0, "mode": "abs", "direction": "higher",
            "flagged": True}
        flagged.append("warm_tenant_floor")
    if not drifts:
        # the prior summary carried none of the tracked keys (e.g. a
        # --quick run): comparing NOTHING must not read as a green gate
        gate["reason"] = ("previous telemetry_summary has no comparable "
                         "metrics (northstar-less prior run?)")
        return cur, gate
    gate.update(checked=True, ok=not flagged, value=0.0 if flagged else 1.0,
                drifts=drifts, flagged=flagged)
    return cur, gate


def _summary_line(emitted):
    """One compact JSON line holding every config's key numbers.

    VERDICT r5 item 7: the driver artifact keeps only the last ~2000 output
    characters, and by round 5 a single full config line outgrew that —
    the artifact captured no parseable metric at all and the README table
    fell back to PROVISIONAL local rows.  Printing this digest LAST puts
    every config (and the north-star/parity essentials) inside any
    truncation window; ``tools/gen_readme_perf.py`` parses it first-class.
    """
    import re

    configs = {}
    headline = {}
    parity_ok = None
    for obj in emitted:
        m = obj.get("metric", "")
        if m.startswith("pallas/scan"):
            parity_ok = obj.get("ok")
            continue
        match = re.match(r"(config\d+b?)\b", m)
        if not match:
            continue
        key = match.group(1)
        entry = {
            "metric": m,
            "value": obj.get("value"),
            "unit": str(obj.get("unit", ""))[:44],
            "vs_baseline": obj.get("vs_baseline"),
            "speedup_vs_cpu_allcore": obj.get("speedup_vs_cpu_allcore"),
        }
        if obj.get("converged_frac") is not None:
            entry["converged_frac"] = obj["converged_frac"]
        if key == "config3":
            headline = obj
            for f in ("vs_target_unscaled", "fit_plus_forecast_series_per_sec",
                      "p50_fit_latency_s"):
                if obj.get(f) is not None:
                    entry[f] = obj[f]
            ns = obj.get("northstar_1m")
            if ns:
                entry["northstar_1m"] = {k: ns.get(k) for k in (
                    "series_total", "wall_s", "converged_frac",
                    "sustained_converged_series_per_sec", "peak_hbm_bytes",
                    "peak_mem_source", "overlap_efficiency",
                    "input_overlap_efficiency",
                    "end_to_end_overlap_efficiency",
                    "zero_per_chunk_align_syncs",
                    "journaled_over_unjournaled",
                    "journaled_bitwise_identical")}
                j = ns.get("journal") or {}
                entry["northstar_1m"]["chunks_resumed"] = j.get(
                    "chunks_resumed")
            sn = obj.get("sharded_northstar")
            if sn and not sn.get("skipped"):
                entry["sharded_northstar"] = {k: sn.get(k) for k in (
                    "series_total", "n_lanes", "wall_s_sharded",
                    "wall_s_single_device", "sharded_speedup",
                    "sharded_converged_series_per_sec",
                    "shard_overlap_efficiency_min",
                    "sharded_bitwise_identical",
                    "wall_s_degraded", "degraded_speedup",
                    "rebalance_overhead", "degraded_bitwise_identical",
                    "degraded_gate_ok")}
            elif sn:
                entry["sharded_northstar"] = sn
            ov = obj.get("oversubscribed_northstar")
            if ov:
                entry["oversubscribed_northstar"] = {k: ov.get(k) for k in (
                    "series_total", "oversubscription_factor",
                    "wall_s_host_resident", "host_over_hbm_throughput",
                    "host_bitwise_identical", "device_footprint_ok",
                    "input_overlap_efficiency")}
            af = obj.get("auto_fit_northstar")
            if af:
                entry["auto_fit_northstar"] = {k: af.get(k) for k in (
                    "series_total", "candidate_orders", "wall_s",
                    "order_series_per_sec", "compile_cache_hit_rate",
                    "fused_speedup", "diff_cache_hits",
                    "fused_selection_agreement",
                    "stage2_spend_share", "winners_speedup",
                    "winners_gate_ok",
                    "winners_stage2_spend_share",
                    "winners_selection_agreement")}
            sv = obj.get("serving_northstar")
            if sv:
                entry["serving_northstar"] = {k: sv.get(k) for k in (
                    "requests", "rows_per_request", "rows_per_sec",
                    "p50_request_latency_s", "p99_request_latency_s",
                    "batch_amplification", "overload_shed_rate",
                    "overload_conserved", "serving_gate_ok")}
            fl = obj.get("fleet_serving_northstar")
            if fl:
                entry["fleet_serving_northstar"] = {k: fl.get(k) for k in (
                    "replicas", "requests", "rows_per_request",
                    "rows_per_sec", "p50_request_latency_s",
                    "p99_request_latency_s", "failover_request_wall_s",
                    "failover_recovery_penalty_s",
                    "failover_bitwise_identical", "fleet_gate_ok")}
            ch = obj.get("chaos_northstar")
            if ch:
                entry["chaos_northstar"] = {k: ch.get(k) for k in (
                    "replicas", "probe_ok_rate", "longest_unavailable_s",
                    "failover_request_wall_s",
                    "failover_bitwise_identical", "standby_read_bitwise",
                    "degraded_reads_per_sec", "write_refused_on_standby",
                    "chaos_gate_ok")}
            fo = obj.get("forecast_northstar")
            if fo:
                entry["forecast_northstar"] = {k: fo.get(k) for k in (
                    "series_total", "horizon", "forecast_rows_per_sec",
                    "forecast_bitwise_identical", "backtest_wall_s",
                    "backtest_windows", "ensemble_overhead",
                    "ensemble_argmin_bitwise", "forecast_gate_ok")}
            de = obj.get("delta_refit_northstar")
            if de:
                entry["delta_refit_northstar"] = {k: de.get(k) for k in (
                    "series_total", "dirty_fraction", "delta_speedup",
                    "delta_bitwise_identical", "warm_speedup",
                    "warm_bitwise_vs_warm_reference", "delta_gate_ok")}
            tk = obj.get("tick_loop_northstar")
            if tk:
                entry["tick_loop_northstar"] = {k: tk.get(k) for k in (
                    "series_total", "cycles", "ticks_per_cycle",
                    "published_rows_per_sec", "cycle_wall_s_mean",
                    "warm_chained", "backtest_windows",
                    "backtest_adopted", "backtest_delta_speedup",
                    "backtest_bitwise_identical", "tick_loop_gate_ok")}
            wt = obj.get("warm_tenant_northstar")
            if wt:
                entry["warm_tenant_northstar"] = {k: wt.get(k) for k in (
                    "tenants", "rows_per_tenant", "passes",
                    "warm_tenant_speedup", "routes_ok",
                    "warm_selection_exact", "exhaustive_agreement",
                    "warm_tenant_gate_ok")}
        configs[key] = entry
    line = {
        "metric": "bench_summary: all configs, tail-truncation-proof "
                  "(parsed by tools/gen_readme_perf.py)",
        "value": headline.get("value"),
        "unit": headline.get("unit"),
        "vs_baseline": headline.get("vs_baseline"),
        "parity_ok": parity_ok,
        "configs": configs,
    }
    # fit inside the truncation window: shorten metric strings (the scale
    # regexes need ~110 chars), then drop them entirely as a last resort
    for trim in (110, 80, 0):
        if len(json.dumps(line)) <= 1950:
            break
        for e in configs.values():
            e["metric"] = e["metric"][:trim]
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,4,5,3",
                    help="comma-separated subset of 1..5 (3 always prints "
                         "last, followed only by the compact summary line)")
    ap.add_argument("--quick", action="store_true", help="small sizes (CI smoke)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the headline config")
    args = ap.parse_args()
    wanted = [c.strip() for c in args.configs.split(",") if c.strip()]

    # persistent compilation cache: a restarted bench (or a journaled
    # resume) reads compiled executables from disk instead of re-paying
    # trace+compile for every fit program.  Placed by the package's one
    # resolver, BEFORE the first device use.
    from spark_timeseries_tpu.utils import compile_cache as _compile_cache

    _cc_dir = _compile_cache.configure()

    # the sharded north-star (ISSUE 6) needs >=2 local devices: on hosts
    # whose backend is the CPU, force virtual XLA CPU devices BEFORE the
    # backend initializes (one per core, capped at 8 — the v5e-8 layout).
    # Only the Host platform is affected, so a TPU-backed run is untouched;
    # an operator's explicit XLA_FLAGS count always wins.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        n_virt = max(2, min(8, os.cpu_count() or 1))
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_virt}").strip()

    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.quick:
        # a measurement path that finds no chip fails; it does not fall
        # back to shrunk sizes under the same metric names
        print(f"bench.py: no TPU (jax platform {platform!r}); nothing "
              "measured.  --quick runs the CPU control-flow smoke.",
              file=sys.stderr)
        return 1
    _hbm_peak_gbps()  # an unknown device_kind fails here, before measuring
    n_chips = len(jax.devices())
    if platform == "cpu":
        # virtual CPU devices are mesh lanes, not chips: keep the
        # north-star target scaled to ONE host, as before
        n_chips = 1
    _progress(f"persistent compile cache: {_cc_dir}")

    emitted = []

    def track(obj):
        emitted.append(obj)
        _emit(obj)

    _progress(f"platform={platform} chips={n_chips}; parity gate...")
    # a gate trip or a kernel compile failure raises out of main(): numbers
    # measured on kernels that disagree with their reference are not kept
    parity = check_backend_parity(jnp, on_tpu)
    # ok=True ONLY when the gate actually ran and passed; an off-TPU run
    # (checked=False) must not read as a pass downstream
    parity = {"ok": bool(parity.get("checked")), **parity}
    track({"metric": "pallas/scan on-device parity gate", "value": 1.0,
           "unit": "ok", "vs_baseline": 1.0, **parity})

    if "1" in wanted:
        _progress("config 1...")
        track(bench_autocorr(jnp, args.quick))
        _progress("config 1b...")
        track(bench_autocorr_at_scale(jnp, args.quick, on_tpu))
    if "2" in wanted:
        _progress("config 2...")
        track(bench_fill_chain(jnp, args.quick, on_tpu))
    if "4" in wanted:
        _progress("config 4...")
        track(bench_garch(jnp, args.quick, on_tpu))
    if "5" in wanted:
        _progress("config 5...")
        track(bench_holtwinters(jnp, args.quick, on_tpu))
    if "3" in wanted:
        _progress("config 3 (headline)...")
        if args.profile:
            with jax.profiler.trace(args.profile):
                line = bench_arima_headline(jnp, args.quick, on_tpu, n_chips,
                                            platform, parity)
        else:
            line = bench_arima_headline(jnp, args.quick, on_tpu, n_chips,
                                        platform, parity)
        track(line)
        # telemetry summary + regression gate (ROADMAP satellite): emitted
        # AFTER the headline so the summary survives in the artifact tail
        # for the next run to diff against
        ts_line, gate_line = _telemetry_regression_gate(line)
        track(ts_line)
        track(gate_line)
    # LAST line: the compact all-configs digest — whatever tail the driver
    # keeps, every config's numbers survive
    _emit(_summary_line(emitted))
    return 0


if __name__ == "__main__":
    sys.exit(main())
