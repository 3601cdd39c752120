"""The benchmark's inputs, every one a pure function of ``--seed``.

Panels are made ON the device in one jitted call (a 4 GB panel shipped from
the host would be most of the set-up); request schedules are small numpy
arrays.  One general generator serves every traffic mix: what a mix changes
is its parameters (``README.md`` lists them), never this code.
"""

from __future__ import annotations

import numpy as np

# -- panels ------------------------------------------------------------------

MIX_CLASSES = ("matched", "near_unit_root", "white_noise", "level_shift")


def _lengths_spec(lengths, n_time: int):
    """``None``/``n_time`` -> dense; else ``(dist, lo, hi)``."""
    if lengths is None or lengths == n_time:
        return None
    if isinstance(lengths, int):
        return ("uniform", lengths, lengths)
    lo, hi = int(lengths["min"]), int(lengths["max"])
    if not 2 <= lo <= hi <= n_time:
        raise ValueError(f"lengths {lengths} outside [2, n_time={n_time}]")
    return (lengths.get("dist", "uniform"), lo, hi)


def _block_fn(process_rows, proc_params: dict, n_time: int, mix: dict):
    """``block(key, n) -> [n, n_time]`` f32 with the mix's departures from
    the dense matched panel applied; a departure the mix does not ask for
    adds nothing to the compiled program."""
    import jax
    import jax.numpy as jnp

    shares = dict(mix.get("process_mix") or {"matched": 1.0})
    unknown = set(shares) - set(MIX_CLASSES)
    if unknown:
        raise ValueError(f"unknown process_mix classes {sorted(unknown)}; "
                         f"have {MIX_CLASSES}")
    total = sum(shares.values())
    cum = np.cumsum([shares.get(c, 0.0) / total for c in MIX_CLASSES])
    mixed = shares.get("matched", 0.0) < total
    nur_lo, nur_hi = mix.get("near_unit_root_phi", (0.97, 0.999))
    lengths = _lengths_spec(mix.get("lengths"), n_time)
    pad_side = mix.get("pad_side", "leading")
    if pad_side not in ("leading", "trailing", "both"):
        raise ValueError(f"pad_side {pad_side!r}")
    gap_frac = float(mix.get("gap_frac", 0.0))

    def block(key, n: int):
        k_base, k_cls, k_alt, k_len, k_gap = jax.random.split(key, 5)
        y = process_rows(k_base, n, n_time, proc_params)
        t = jnp.arange(n_time)[None, :]
        if mixed:
            # rows the model does not describe, on the matched row's own
            # scale (s: std of its first differences) and starting level
            k_e, k_phi, k_t0 = jax.random.split(k_alt, 3)
            s = jnp.std(jnp.diff(y, axis=1), axis=1, keepdims=True)
            e = jax.random.normal(k_e, (n, n_time), jnp.float32)
            phi = jax.random.uniform(k_phi, (n,), jnp.float32, nur_lo, nur_hi)

            def ar1(x_prev, e_t):
                x_t = phi * x_prev + e_t
                return x_t, x_t

            _, x = jax.lax.scan(ar1, jnp.zeros((n,), jnp.float32), e.T)
            near_unit = y[:, :1] + s * x.T
            white = jnp.mean(y, axis=1, keepdims=True) + s * e
            t0 = jax.random.randint(k_t0, (n, 1), n_time // 4,
                                    3 * n_time // 4)
            shifted = y + jnp.where(t >= t0, 8.0 * s, 0.0)
            cls = jnp.searchsorted(
                jnp.asarray(cum, jnp.float32),
                jax.random.uniform(k_cls, (n,), jnp.float32))[:, None]
            y = jnp.select([cls == 0, cls == 1, cls == 2],
                           [y, near_unit, white], shifted)
        cut = jnp.zeros((n, n_time), bool)
        if lengths is not None:
            dist, lo, hi = lengths
            k_l, k_side = jax.random.split(k_len)
            u = jax.random.uniform(k_l, (n, 1), jnp.float32)
            if dist == "log-uniform":
                ln = jnp.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
            elif dist == "uniform":
                ln = lo + u * (hi - lo)
            else:
                raise ValueError(f"lengths dist {dist!r}")
            ln = jnp.clip(jnp.round(ln).astype(jnp.int32), lo, hi)
            lead = t < n_time - ln   # valid span ends at the last position
            trail = t >= ln          # valid span starts at the first
            if pad_side == "leading":
                cut = lead
            elif pad_side == "trailing":
                cut = trail
            else:
                cut = jnp.where(jax.random.bernoulli(k_side, 0.5, (n, 1)),
                                lead, trail)
        if gap_frac > 0.0:
            # strictly inside the valid span, so a row keeps its length
            edge = cut | jnp.roll(cut, 1, axis=1) | jnp.roll(cut, -1, axis=1) \
                | (t == 0) | (t == n_time - 1)
            gap = jax.random.uniform(k_gap, (n, n_time)) < gap_frac
            cut = cut | (gap & ~edge)
        if lengths is not None or gap_frac > 0.0:
            y = jnp.where(cut, jnp.nan, y)
        return y.astype(jnp.float32)

    return block


def panel_program(process_rows, proc_params: dict, mix: dict, devices,
                  n_rows: int, n_time: int, block_rows: int):
    """The jitted generator ``(population key data, block order) ->
    [n_rows, n_time]`` f32 over ``devices`` (kept apart from
    :func:`build_panel` so that it can be compiled for a described topology
    without a chip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_dev = len(devices)
    if n_rows % (n_dev * block_rows):
        raise ValueError(f"n_rows={n_rows} is not a multiple of "
                         f"{n_dev} devices x block_rows={block_rows}")
    per_dev = n_rows // n_dev
    block = _block_fn(process_rows, proc_params, n_time, mix)
    mesh = Mesh(np.asarray(devices), ("series",))

    def per_shard(key_data, order):
        # every device its own rows: the key is folded by its place
        key = jax.random.fold_in(jax.random.wrap_key_data(key_data),
                                 jax.lax.axis_index("series"))

        def body(i, panel):
            # block order[i] of the population lands at place i
            rows = block(jax.random.fold_in(key, order[i]), block_rows)
            return jax.lax.dynamic_update_slice(panel, rows,
                                                (i * block_rows, 0))

        return jax.lax.fori_loop(
            0, per_dev // block_rows, body,
            jnp.zeros((per_dev, n_time), jnp.float32))

    fn = jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=(P(), P()),
                               out_specs=P("series", None), check_vma=False))
    return fn, NamedSharding(mesh, P())


BLOCKS_PER_CHUNK = 16


def block_order(seed: int, n_chunks: int, blocks_per_chunk: int):
    """Where ``--seed`` lays the population's blocks down: the chunks in a
    seeded order, and inside each chunk its own blocks in a seeded order."""
    rng = np.random.default_rng([int(seed), 0x0FF5E7])
    return np.concatenate([c * blocks_per_chunk
                           + rng.permutation(blocks_per_chunk)
                           for c in rng.permutation(n_chunks)])


def build_panel(process_rows, proc_params: dict, mix: dict, seed: int,
                devices, n_rows: int, n_time: int, chunk_rows: int,
                population_seed: int = 0):
    """``[n_rows, n_time]`` f32 on ``devices`` in ONE jitted call.

    The ROWS are a population fixed by the configuration
    (``population_seed``), generated in blocks of a sixteenth of a chunk.
    ``seed`` draws the order of the chunks and the order of the blocks
    inside each chunk (:func:`block_order`): two seeds give different
    panels and different request pools, made of the same chunks of the
    same rows.  The work a fit takes depends on its rows — a lockstep
    optimizer runs a chunk as long as its slowest row, and one row in a
    million needs the retry ladder — so freshly drawn panels differed by
    1.5-2% in what a walk costs and in which programs they need, and the
    same rows cut into other chunks still by 1.3-1.5% (my chip runs 1-3,
    PR 23), against 0.35% between two runs of one seed.  No window length
    averages that out.  With the chunks fixed, a run's work and its set-up
    are the same from seed to seed, and runs differ by the system.

    The loop carry is updated in place, so the temporaries are
    block-sized.  Each device generates its own rows under the series
    sharding the sharded walk places its lanes with, so placing the lanes
    moves nothing; on one device the result is committed to that device
    alone."""
    import jax
    import jax.numpy as jnp

    n_dev = len(devices)
    if n_rows % (n_dev * chunk_rows):
        raise ValueError(f"n_rows={n_rows} is not a multiple of {n_dev} "
                         f"devices x chunk_rows={chunk_rows}")
    per_chunk = min(BLOCKS_PER_CHUNK, chunk_rows)
    fn, replicated = panel_program(process_rows, proc_params, mix, devices,
                                   n_rows, n_time, chunk_rows // per_chunk)
    order = block_order(seed, n_rows // n_dev // chunk_rows, per_chunk)
    key_data = jax.random.key_data(jax.random.key(int(population_seed)))
    panel = jax.block_until_ready(fn(
        jax.device_put(key_data, replicated),
        jax.device_put(jnp.asarray(order, jnp.int32), replicated)))
    if n_dev == 1:
        panel = jax.device_put(panel, devices[0])  # same buffer, plain
    return panel


# -- open-loop request schedules ---------------------------------------------


def _draw_rows(spec, q: np.ndarray) -> np.ndarray:
    """Row counts at quantiles ``q`` of the mix's ``rows`` distribution."""
    if isinstance(spec, int):
        return np.full(q.shape, spec, np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec.get("dist", "uniform")
    if dist == "log-uniform":
        r = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    elif dist == "uniform":
        r = lo + q * (hi - lo)
    else:
        raise ValueError(f"rows dist {dist!r}")
    return np.clip(np.round(r).astype(np.int64), lo, hi)


def _cumulative_intensity(arrival: dict, seconds: float, grid: np.ndarray):
    """Relative cumulative arrival intensity over ``grid`` (its scale does
    not matter: the count is fixed, see :func:`request_schedule`)."""
    kind = arrival.get("kind", "poisson")
    if kind in ("poisson", "paced"):
        return grid / seconds
    if kind == "onoff":
        factor = float(arrival["factor"])
        on_s, period_s = float(arrival["on_s"]), float(arrival["period_s"])
        rate = np.where(np.mod(grid, period_s) < on_s, factor, 1.0)
        cum = np.concatenate([[0.0], np.cumsum(rate[:-1] * np.diff(grid))])
        return cum / cum[-1]
    raise ValueError(f"arrival kind {kind!r}")


def request_schedule(traffic: dict, seed: int, seconds: float,
                     pool_rows: int) -> dict:
    """The open loop's requests for a window of ``seconds``: when each is
    due, how many rows it has, whose it is, and where in the request pool
    its rows start.

    When and how large come from the mix's ``schedule_seed`` where it has
    one (whose and which rows always from ``seed``): the tail of a window
    is made by the few large requests that meet others, and which ones meet
    differs from one drawn schedule to the next by 7-10% in the 95th
    percentile (12 runs, my chip run 4, PR 23) — as with the panel's
    population, that is a difference between inputs, which no later PR
    could be told from.

    A fixed amount of work from the seed, so that runs differ by the
    system and not by the draw: exactly ``round(rate * seconds)`` arrivals
    (a Poisson process conditioned on its count is the sorted uniforms, or
    for on/off bursts their image under the inverse cumulative intensity),
    and row counts drawn one from each of as many equal-probability strata
    of the ``rows`` distribution, then shuffled.
    """
    who = np.random.default_rng([int(seed), 0x5E7])
    rng = who if traffic.get("schedule_seed") is None else \
        np.random.default_rng([int(traffic["schedule_seed"]), 0x5C4ED])
    n = max(1, int(round(float(traffic["rate"]) * seconds)))
    grid = np.linspace(0.0, seconds, max(2, int(seconds * 1000) + 1))
    arrival = traffic.get("arrival", {})
    cum = _cumulative_intensity(arrival, seconds, grid)
    if arrival.get("kind") == "paced":
        # one arrival in each of n equal slots, anywhere inside it
        u = (np.arange(n) + rng.random(n)) / n
    else:
        u = np.sort(rng.random(n))
    due = np.interp(u, cum, grid)
    rows = _draw_rows(traffic["rows"], (np.arange(n) + rng.random(n)) / n)
    rng.shuffle(rows)
    if rows.max() > pool_rows:
        raise ValueError(f"a request of {rows.max()} rows does not fit the "
                         f"pool of {pool_rows}")
    k = np.arange(1, int(traffic.get("tenants", 1)) + 1, dtype=np.float64)
    share = k ** -float(traffic.get("skew", 0.0))
    tenant = who.choice(len(k), size=n, p=share / share.sum())
    offset = (who.random(n) * (pool_rows - rows + 1)).astype(np.int64)
    return {"due_s": due, "rows": rows, "tenant": tenant, "offset": offset}
