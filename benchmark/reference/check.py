"""The comparison that decides whether fitted parameters are right.

One-sided: the system's parameters, evaluated under the plain reference
objective in float64, may lose at most ``LOGLIK_GAP_MAX`` units of
log-likelihood against the optimum ``scipy.optimize`` finds on the same
row.  ``README.md`` beside this file gives the reason for the number.
"""

import numpy as np

LOGLIK_GAP_MAX = 0.1


def loglik_gaps(reference, model_kwargs, rows, params) -> np.ndarray:
    """Per row: ``loglik(reference optimum) - loglik(system's params)``
    under the reference objective (both are concentrated Gaussian
    likelihoods of a sum of squares, so the gap is ``0.5 n_eff
    log(ss_sys / ss_ref)``).  Negative where the system found the better
    optimum; ``inf`` where its parameters are not finite."""
    gaps = np.empty(len(rows))
    for i, (y, par) in enumerate(zip(rows, params)):
        if not np.all(np.isfinite(par)):
            gaps[i] = np.inf
            continue
        ss_sys, n_eff = reference.objective(par, y, model_kwargs)
        ss_ref, _ = reference.objective(reference.optimum(y, model_kwargs),
                                        y, model_kwargs)
        gaps[i] = 0.5 * n_eff * np.log(ss_sys / ss_ref)
    return gaps


def recovery(params, spec) -> list:
    """Median fitted parameter against the generating value, for each
    entry ``{"name", "index", "value", "tol"}`` of a configuration's
    ``recovery`` list: right, not merely finite."""
    med = np.nanmedian(np.asarray(params, np.float64), axis=0)
    return [{"name": r["name"], "median": float(med[r["index"]]),
             "value": r["value"], "tol": r["tol"],
             "ok": bool(abs(med[r["index"]] - r["value"]) <= r["tol"])}
            for r in spec]
