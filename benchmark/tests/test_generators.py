"""The inputs are a pure function of ``--seed``, and a mix's parameters do
what ``README.md`` says (tiny sizes, CPU)."""

import jax
import numpy as np
import pytest

from benchmark import generators as g
from benchmark.processes import integrated_arma, seasonal_level_trend

ARMA = {"phi": 0.6, "theta": 0.3}
HW = {"level": 10.0, "trend": 0.02, "amplitude": 2.0, "period": 24,
      "noise": 0.3}
SERVE = {"rate": 12.0, "rows": {"dist": "log-uniform", "min": 64,
                                "max": 4096}, "tenants": 8, "skew": 1.1}


def panel(process, params, mix, seed, rows=512, n_time=128):
    return np.asarray(g.build_panel(process.rows, params, mix, seed,
                                    jax.devices()[:1], rows, n_time, 128))


@pytest.mark.parametrize("process,params", [(integrated_arma, ARMA),
                                            (seasonal_level_trend, HW)])
def test_panel_is_a_function_of_the_seed(process, params):
    a, b = panel(process, params, {}, 7), panel(process, params, {}, 7)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert np.isfinite(a).all()
    # blocks differ from one another: the key is folded per block
    assert not np.array_equal(a[:128], a[128:256])
    # another seed: the same chunks of the same rows, laid down otherwise
    c = panel(process, params, {}, 8)
    assert not np.array_equal(a, c)
    order = lambda y: y[np.lexsort(y.T[::-1])]  # noqa: E731
    chunks = lambda y: sorted(order(y[i:i + 128]).tobytes()  # noqa: E731
                              for i in range(0, 512, 128))
    assert chunks(a) == chunks(c)
    assert [a[i].tobytes() for i in range(0, 512, 128)] \
        != [c[i].tobytes() for i in range(0, 512, 128)]
    other = np.asarray(g.build_panel(process.rows, params, {}, 7,
                                     jax.devices()[:1], 512, 128, 128,
                                     population_seed=1))
    assert not np.array_equal(order(a), order(other))


def test_arma_panel_is_the_process_the_model_describes():
    y = panel(integrated_arma, ARMA, {}, 3, rows=512, n_time=512)
    x = np.diff(y, axis=1).astype(np.float64)
    x -= x.mean(axis=1, keepdims=True)
    rho1 = np.mean(np.sum(x[:, 1:] * x[:, :-1], axis=1)
                   / np.sum(x * x, axis=1))
    phi, theta = ARMA["phi"], ARMA["theta"]
    expect = (1 + phi * theta) * (phi + theta) / (1 + 2 * phi * theta
                                                  + theta ** 2)
    assert abs(rho1 - expect) < 0.02


def test_mix_departures():
    mix = {"process_mix": {"matched": 0.6, "near_unit_root": 0.2,
                           "white_noise": 0.1, "level_shift": 0.1},
           "gap_frac": 0.1, "pad_side": "both",
           "lengths": {"dist": "log-uniform", "min": 32, "max": 128}}
    y = panel(seasonal_level_trend, HW, mix, 5, rows=1024)
    assert np.array_equal(y, panel(seasonal_level_trend, HW, mix, 5,
                                   rows=1024), equal_nan=True)
    valid = np.isfinite(y)
    lengths = valid.shape[1] - np.argmax(valid[:, ::-1], axis=1) \
        - np.argmax(valid, axis=1)
    assert lengths.min() >= 32 and lengths.max() <= 128
    lead, trail = ~valid[:, 0], ~valid[:, -1]
    assert 0.3 < lead.mean() < 0.7 and 0.3 < trail.mean() < 0.7
    assert not (lead & trail).any()
    dense = panel(seasonal_level_trend, HW, {}, 5, rows=1024)
    changed = np.mean(np.any(valid & (np.abs(y - dense) > 1e-6), axis=1))
    assert 0.3 < changed < 0.5  # about 0.4 of the rows are not matched
    with pytest.raises(ValueError):
        panel(seasonal_level_trend, HW, {"process_mix": {"odd": 1.0}}, 1)


def test_panel_across_devices_is_placed_by_series():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    y = g.build_panel(integrated_arma.rows, ARMA, {}, 7, devs[:4], 2048,
                      64, 128)
    assert [s.data.shape for s in y.addressable_shards] == [(512, 64)] * 4
    rows = np.asarray(y)
    assert not np.array_equal(rows[:512], rows[512:1024])


def test_schedule_is_fixed_work_from_the_seed():
    a = g.request_schedule(SERVE, 5, 30.0, 131072)
    b = g.request_schedule(SERVE, 5, 30.0, 131072)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    c = g.request_schedule(SERVE, 6, 30.0, 131072)
    assert not np.array_equal(a["due_s"], c["due_s"])
    # the same count and, by stratified sizes, nearly the same rows
    assert len(a["due_s"]) == len(c["due_s"]) == 360
    assert abs(a["rows"].sum() - c["rows"].sum()) < 0.01 * a["rows"].sum()
    assert a["rows"].min() >= 64 and a["rows"].max() <= 4096
    assert np.all(np.diff(a["due_s"]) >= 0) and a["due_s"][-1] < 30.0
    assert np.all(a["offset"] + a["rows"] <= 131072)
    share = np.bincount(a["tenant"], minlength=8) / 360
    assert share[0] > 0.25 and share[0] > 2 * share[3]


def test_paced_arrivals_are_one_to_a_slot():
    s = g.request_schedule(dict(SERVE, arrival={"kind": "paced"}), 5, 30.0,
                           131072)
    assert len(s["due_s"]) == 360
    assert np.array_equal(np.floor(s["due_s"] * 12.0), np.arange(360))
    again = g.request_schedule(dict(SERVE, arrival={"kind": "paced"}), 6,
                               30.0, 131072)
    assert not np.array_equal(s["due_s"], again["due_s"])


def test_bursts_keep_the_mean_rate():
    mix = dict(SERVE, arrival={"kind": "onoff", "factor": 5.0, "on_s": 1.0,
                               "period_s": 5.0})
    s = g.request_schedule(mix, 5, 30.0, 131072)
    assert len(s["due_s"]) == 360
    on = np.mod(s["due_s"], 5.0) < 1.0
    assert 0.45 < on.mean() < 0.65  # 5 / (5 + 4) of the arrivals
    with pytest.raises(ValueError):
        g.request_schedule(dict(SERVE, arrival={"kind": "odd"}), 1, 5.0, 9000)


def test_schedule_seed_fixes_when_and_how_large():
    mix = dict(SERVE, schedule_seed=0)
    a = g.request_schedule(mix, 5, 30.0, 131072)
    b = g.request_schedule(mix, 6, 30.0, 131072)
    assert np.array_equal(a["due_s"], b["due_s"])
    assert np.array_equal(a["rows"], b["rows"])
    assert not np.array_equal(a["offset"], b["offset"])
    assert not np.array_equal(a["tenant"], b["tenant"])
    c = g.request_schedule(dict(SERVE, schedule_seed=1), 5, 30.0, 131072)
    assert not np.array_equal(a["due_s"], c["due_s"])
