"""Fits with the timed path broken underneath, named by a configuration's
``model.fit`` in ``test_rehearse.py``: what the reference must refuse."""

from spark_timeseries_tpu.models import arima


def arima_params_shifted(y, **kwargs):
    """``arima.fit`` with every answer altered where it is produced: the
    coefficients moved by 0.2, everything else as fitted (converged, OK,
    journaled and re-read bitwise)."""
    res = arima.fit(y, **kwargs)
    return res._replace(params=res.params + 0.2)
