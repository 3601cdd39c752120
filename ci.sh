#!/usr/bin/env bash
# CI entry point: run the full test suite on a simulated 8-device CPU mesh —
# the analog of the reference's Travis `mvn scalatest:test` single-node run
# (SURVEY.md §4): multi-chip logic is exercised with no TPU attached, exactly
# as Spark local[n] stood in for a cluster.
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
# the same default the package's resolver uses (utils/compile_cache.py)
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"

# invariant linter (ISSUE 13): the codebase's cross-cutting contracts —
# host-sync-free hot paths, config-hash knob coverage, journal write
# ownership, lock-map discipline, obs inertness, nondeterminism bans —
# are machine-checked BEFORE any test runs.  The self-test runs first and
# seeds a violation of every contract into each checker: a linter whose
# checkers silently stopped matching would otherwise pass vacuously and
# CI would go green on a broken guard.  Then the real lint runs against
# the committed (EMPTY) baseline: any NEW finding fails CI with the
# machine-readable report on stderr.
python -m tools.lint --self-test
python -m tools.lint --json > /tmp/ci_lint.json || {
  echo "ci.sh: ststpu-lint found NEW contract violations" >&2
  cat /tmp/ci_lint.json >&2
  echo "ci.sh: run 'python -m tools.lint --explain <rule>' for the" >&2
  echo "       contract text and the inline-waiver syntax" >&2
  exit 1
}

# -rs surfaces every skip with its reason: the 2-process jax.distributed
# smoke test skips on a chronically slow host, and that must be VISIBLE in
# CI output, not silently folded into the pass count (VERDICT r3 weak #4)
# test_reliability.py is excluded here and run below under escalated
# warnings — once per CI invocation, not twice
python -m pytest tests/ -q -rs --ignore=tests/test_reliability.py "$@" \
  | tee /tmp/ci_pytest_out.txt
if grep -qE "skipped" /tmp/ci_pytest_out.txt; then
  echo "ci.sh: NOTE — skipped tests present (reasons above)." >&2
fi

# fault-injection sweep (ISSUE 1): the reliability module re-runs with
# RuntimeWarnings escalated to errors, so an unhandled-NaN warning escaping
# a fit path (invalid-value reductions, divide-by-zero in an objective)
# fails CI instead of scrolling by.  Scoped to the reliability tests: the
# wider suite intentionally feeds models NaN panels whose warnings are the
# point under test.
python -m pytest tests/test_reliability.py -q -rs -W error::RuntimeWarning "$@"

# kill-and-resume smoke (ISSUE 2): a journaled 4-chunk CPU fit is SIGKILLed
# after committing chunk 2, resumed from the write-ahead journal, and the
# resumed result must be BITWISE-identical to an uninterrupted run with the
# manifest accounting for all 4 chunks — real process death, not an
# exception (tests/_journal_worker.py orchestrates three worker processes)
python tests/_journal_worker.py --smoke

# pipelined-committer smoke (ISSUE 4): the pipelined walk (background
# committer, bounded queue) must be bitwise-identical to the serial
# pipeline=False walk, report its overlap accounting, and leave a manifest
# the budget advisor can turn into next-run knobs
PIPE_SMOKE_DIR=$(python - <<'EOF'
import os, tempfile
import numpy as np
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

rng = np.random.default_rng(0)
y = np.cumsum(rng.normal(size=(32, 96)).astype(np.float32), axis=1)
root = tempfile.mkdtemp(prefix="pipe_smoke_")
kw = dict(chunk_rows=8, resilient=False, order=(1, 0, 0), max_iters=15)
ser = rel.fit_chunked(arima.fit, y, checkpoint_dir=os.path.join(root, "ser"),
                      pipeline=False, **kw)
pipe = rel.fit_chunked(arima.fit, y, checkpoint_dir=os.path.join(root, "pipe"),
                       pipeline_depth=3, **kw)
for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
    np.testing.assert_array_equal(np.asarray(getattr(ser, f)),
                                  np.asarray(getattr(pipe, f)), err_msg=f)
p = pipe.meta["pipeline"]
assert p["commits_background"] == 4, p
assert p["hidden_commit_s"] <= p["commit_wall_s"] + 1e-9, p
print(root)
EOF
)
python tools/advise_budget.py "$PIPE_SMOKE_DIR/pipe" \
  | grep -q "pipeline_depth" \
  || { echo "ci.sh: advise_budget did not print suggestions" >&2; exit 1; }
rm -rf "$PIPE_SMOKE_DIR"

# dispatch-ahead input smoke (ISSUE 5): a short journaled PREFETCHED walk
# (static align plan + background slice staging, telemetry on) must be
# bitwise-identical to the serial walk, journal its input-staging overlap
# into the manifest telemetry block, pass the obs_report schema gate, and
# give the budget advisor enough to suggest prefetch_depth and the align
# hint for the next run
PREFETCH_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

rng = np.random.default_rng(0)
y = np.cumsum(rng.normal(size=(32, 96)).astype(np.float32), axis=1)
root = tempfile.mkdtemp(prefix="prefetch_smoke_")
kw = dict(chunk_rows=8, resilient=False, order=(1, 0, 0), max_iters=15)
ser = rel.fit_chunked(arima.fit, y, pipeline=False, **kw)
obs.enable(os.path.join(root, "events.jsonl"))
pre = rel.fit_chunked(arima.fit, y, prefetch_depth=2,
                      checkpoint_dir=os.path.join(root, "journal"), **kw)
obs.disable()
for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
    np.testing.assert_array_equal(np.asarray(getattr(ser, f)),
                                  np.asarray(getattr(pre, f)), err_msg=f)
p = pre.meta["pipeline"]
assert p["staged_hits"] == 3 and p["staged_misses"] == 1, p
assert p["hidden_staging_s"] <= p["staging_wall_s"] + 1e-9, p
assert pre.meta["align_mode"] in ("dense", "no-trailing", "general")
# the manifest records the staging overlap for the budget advisor
m = json.load(open(os.path.join(root, "journal", "manifest.json")))
st = m["telemetry"]["input_staging"]
assert st["chunks_staged"] == 3 and "input_overlap_efficiency" in st, st
assert m["telemetry"]["align_mode"] == pre.meta["align_mode"]
print(root)
EOF
)
python tools/obs_report.py --check "$PREFETCH_SMOKE_DIR/events.jsonl" \
  --manifest "$PREFETCH_SMOKE_DIR/journal"
python tools/advise_budget.py "$PREFETCH_SMOKE_DIR/journal" \
  | grep -q "prefetch_depth" \
  || { echo "ci.sh: advise_budget did not suggest prefetch_depth" >&2; exit 1; }
python tools/advise_budget.py "$PREFETCH_SMOKE_DIR/journal" \
  | grep -q "align_mode" \
  || { echo "ci.sh: advise_budget did not report the align plan" >&2; exit 1; }
rm -rf "$PREFETCH_SMOKE_DIR"

# telemetry smoke (ISSUE 3): a small journaled chunked fit runs with the
# obs plane enabled; the JSONL event log AND the manifest's embedded
# telemetry block (per-chunk compile/execute spans, ladder counters,
# non-null peak memory) must validate under the schema checker
OBS_SMOKE_DIR=$(python - <<'EOF'
import os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

root = tempfile.mkdtemp(prefix="obs_smoke_")
obs.enable(os.path.join(root, "events.jsonl"))
rng = np.random.default_rng(0)
y = np.cumsum(rng.normal(size=(32, 96)).astype(np.float32), axis=1)
res = rel.fit_chunked(arima.fit, y, chunk_rows=4, order=(1, 0, 0),
                      max_iters=15,
                      checkpoint_dir=os.path.join(root, "journal"))
assert "telemetry" in res.meta, "telemetry summary missing from meta"
obs.disable()
print(root)
EOF
)
python tools/obs_report.py --check "$OBS_SMOKE_DIR/events.jsonl" \
  --manifest "$OBS_SMOKE_DIR/journal"
python tools/inspect_journal.py "$OBS_SMOKE_DIR/journal" \
  | grep -q "telemetry (obs run" \
  || { echo "ci.sh: inspect_journal did not print the telemetry summary" >&2; exit 1; }
rm -rf "$OBS_SMOKE_DIR"

# sharded kill-and-resume smoke (ISSUE 6): a journaled SHARDED walk (8
# forced CPU devices, one prefetch->compute->commit lane per device) is
# SIGKILLed mid-job with several lanes in flight, resumed, and the resumed
# result must be BITWISE-identical to an uninterrupted sharded run AND to
# the single-device walk of the same panel, with exactly ONE merged job
# manifest (written by shard/process 0) accounting for every chunk
python tests/_sharded_worker.py --smoke

# elastic lane smoke (ISSUE 11): a journaled sharded walk with ONE LANE
# KILLED mid-job must complete on the surviving lanes — the dead lane
# retried, quarantined, its uncommitted chunks re-staged and recomputed
# by survivors, its committed shards adopted — bitwise-identical to the
# uninterrupted single-device walk, with the quarantine + owner-tagged
# reassignment journaled in the merged manifest; then the SAME degraded
# job is SIGKILLed mid-rebalance and resumed with the lane healthy:
# quarantine must compose with crash-resume (the resume re-admits the
# quarantined device and replays only truly-uncommitted work), again
# bitwise vs the single-device walk
python tests/_sharded_worker.py --elastic-smoke

# lock-discipline runtime smoke (ISSUE 13): the declared _protected_by_
# maps — the same ones the static lock-map checker verifies lexically —
# are enforced DYNAMICALLY on a real workload: every registered
# concurrency class is instrumented with owner-tracking lock proxies,
# then (1) a seeded off-lock mutation must be CAUGHT (the tracker cannot
# pass vacuously), (2) a journaled pipelined+sharded+elastic walk with a
# fault-injected straggler lane (steals cross-thread) and (3) a resident
# FitServer under a request_storm burst must both complete with ZERO
# violations — while staying bitwise-identical to the uninstrumented run
python tests/_lockdiscipline_worker.py --smoke

# serving kill-and-restart smoke (ISSUE 12): a resident FitServer under a
# request storm — several tenants micro-batched into shared chunked walks,
# one tenant injected slow — is SIGKILLed MID-COMMIT after 2 durable chunk
# commits, restarted on the same root, and must re-answer EVERY admitted
# request bitwise-identically to an uninterrupted server (in-flight batch
# journals resumed, only uncommitted chunks replayed; unbatched requests
# re-enqueued), with the Prometheus textfile it streamed mid-run still
# valid (atomic writes: a scraper never sees a torn file)
python tests/_serving_worker.py --smoke

# fleet failover smoke (ISSUE 16): two FleetReplica processes share one
# checkpoint root under the lease/fencing protocol; the fleet is stormed
# through the socket client (direct submits + a run_backtest(server=)
# leg), the primary is REALLY SIGKILLed mid-commit after 3 durable chunk
# commits, and the surviving standby must take the lease over (higher
# fencing token) and RE-ANSWER every in-flight request bitwise vs an
# uninterrupted single server — then the restarted zombie must be fenced
# back to standby instead of splicing stale bytes. The runtime lock
# tracker rides the survivor and the orchestrator's client retry paths.
python tests/_fleet_worker.py --smoke

# fleet warm-routing smoke (ISSUE 19): tenant auto-fit profiles live on
# the SHARED fleet root, so a failover continues WARM — a tenant's first
# submit routes "new" (full stepwise search) on the primary and lands a
# durable profile; the primary is REALLY SIGKILLed; the surviving
# standby classifies the identical resubmit "stable" off the dead
# primary's profile (stage 1 skipped entirely) with bitwise-equal
# per-row winning orders, and a stale-token holder is refused the
# profile write path (FencedError BEFORE bytes land — the zombie cannot
# clobber the survivor's warm state)
python tests/_fleet_worker.py --warm-smoke

# warm-routing tooling smoke (ISSUE 19): two identical auto-fit submits
# on one serving root must route new -> stable with an unchanged
# selection, leave a stepwise search journal that passes the obs_report
# manifest gate (per-pass partition of the trial walk), and give the
# budget advisor a tenant-profile table (stepwise seed sizing + the
# stable tenant's cell_rows advice) — note a warm AUTO root has ZERO
# batch journals (auto submits bypass the micro-batcher), which is
# exactly the path the advisor's profile rendering must survive
WARM_SMOKE_DIR=$(python - <<'EOF'
import os, tempfile
import numpy as np
from spark_timeseries_tpu import obs, serving

root = tempfile.mkdtemp(prefix="warm_smoke_")
rng = np.random.default_rng(11)
e = rng.normal(size=(8, 96)).astype(np.float32)
y = np.zeros_like(e)
for t in range(1, y.shape[1]):
    y[:, t] = 0.6 * y[:, t - 1] + e[:, t]
kw = dict(max_iters=25, stepwise_max_passes=2, stepwise_max_order=1)
obs.enable(os.path.join(root, "events.jsonl"))
with serving.FitServer(root, cell_rows=8) as srv:
    r1 = srv.submit("acme", y, "panel_auto", request_id="auto-1",
                    warm_routing=True, **kw).result(timeout=600)
    r2 = srv.submit("acme", y, "panel_auto", request_id="auto-2",
                    warm_routing=True, **kw).result(timeout=600)
obs.disable()
assert r1.meta["auto"]["route"] == "new", r1.meta["auto"]
assert r2.meta["auto"]["route"] == "stable", r2.meta["auto"]
assert r1.meta["auto"]["order_index"] == r2.meta["auto"]["order_index"]
h = srv.health()["counters"]
assert h["route_new"] == 1 and h["route_stable"] == 1 \
    and h["profile_updates"] == 2, h
print(root)
EOF
)
python tools/obs_report.py --check "$WARM_SMOKE_DIR/events.jsonl" \
  --manifest "$WARM_SMOKE_DIR/auto/auto-1"
python tools/advise_budget.py "$WARM_SMOKE_DIR" > /tmp/ci_warm_advise.txt
grep -q "tenant profiles" /tmp/ci_warm_advise.txt \
  || { echo "ci.sh: advise_budget did not render the tenant profiles" >&2; exit 1; }
grep -q "stepwise seeds" /tmp/ci_warm_advise.txt \
  || { echo "ci.sh: advise_budget did not size the stepwise seeds" >&2; exit 1; }
rm -rf "$WARM_SMOKE_DIR"

# chaos soak smoke (ISSUE 17): a SEEDED chaos schedule (pause + SIGKILL
# the primary mid-storm) runs against a 2-replica fleet with write-ahead
# disk faults armed on the survivor and HMAC wire auth on every frame;
# the invariant checker must find conservation (every admitted request
# answered), bitwise answers vs an uninterrupted reference (and on
# re-poll), monotone lease fencing, and read availability within bound —
# standby reads cover the leaderless window, a lease-less standby serves
# durable + scratch reads bitwise, refuses writes, and the wrong wire
# secret is refused terminally.  The survivor's obs stream must pass the
# degradation-ladder telemetry gate, and the durable chaos manifest must
# give the budget advisor enough to suggest the next soak's client knobs.
# With tracing on (ISSUE 18), obs_report --fleet must merge the copied
# per-process streams + clock sidecars + chaos manifest and reconstruct
# fit-1 — a request whose primary was SIGKILLed mid-commit — into one
# cross-process causal timeline with exactly one completed terminal.
CHAOS_SMOKE_DIR=$(mktemp -d -t chaos_smoke_XXXXXX)
python tests/_chaos_worker.py --smoke --out "$CHAOS_SMOKE_DIR"
python tools/obs_report.py --check --degradation "$CHAOS_SMOKE_DIR/obs_b.jsonl"
python tools/obs_report.py --fleet "$CHAOS_SMOKE_DIR" --check --trace fit-1
python tools/advise_budget.py "$CHAOS_SMOKE_DIR" \
  | grep -q "suggest for the next soak" \
  || { echo "ci.sh: advise_budget did not read the chaos manifest" >&2; exit 1; }
rm -rf "$CHAOS_SMOKE_DIR"

# serving tooling smoke (ISSUE 12): a short server run with telemetry on
# must leave (a) a prom textfile that passes the obs_report --prom gate —
# exposition syntax + every registry metric present under its mapped name,
# so a renamed counter cannot silently vanish from dashboards — and (b) a
# server.json + per-batch journals the budget advisor's serving mode turns
# into next-life knobs (cell_rows, pipeline depth, overload evidence)
SERVING_SMOKE_DIR=$(python - <<'EOF'
import os, tempfile
import numpy as np
from spark_timeseries_tpu import obs, serving

root = tempfile.mkdtemp(prefix="serving_smoke_")
rng = np.random.default_rng(0)
e = rng.normal(size=(24, 96)).astype(np.float32)
y = np.zeros_like(e)
for t in range(1, y.shape[1]):
    y[:, t] = 0.6 * y[:, t - 1] + e[:, t]
obs.enable(os.path.join(root, "events.jsonl"))
srv = serving.FitServer(root, cell_rows=8, batch_window_s=0.05,
                        prom_path=os.path.join(root, "fits.prom"),
                        prom_interval_s=0.0)
# submit BEFORE start(): the three requests deterministically share the
# first batch instead of racing the coalescing window on a loaded box
ts = [srv.submit(f"tenant{i}", y[8*i:8*(i+1)], "arima",
                 order=(1, 0, 0), max_iters=15) for i in range(3)]
srv.start()
rs = [t.result(timeout=600) for t in ts]
srv.stop()
obs.disable()
assert rs[0].meta["batch_members"] == 3, rs[0].meta  # coalesced into ONE walk
h = srv.health()
assert h["counters"]["completed"] == 3 and h["state"] == "stopped", h
print(root)
EOF
)
python tools/obs_report.py --check "$SERVING_SMOKE_DIR/events.jsonl" \
  --prom "$SERVING_SMOKE_DIR/fits.prom"
python tools/advise_budget.py "$SERVING_SMOKE_DIR" \
  | grep -q "cell_rows" \
  || { echo "ci.sh: advise_budget --serving did not suggest cell_rows" >&2; exit 1; }
rm -rf "$SERVING_SMOKE_DIR"

# host-resident kill-and-resume smoke (ISSUE 7): a journaled walk over a
# panel that lives in HOST RAM — 4x oversubscribed against a virtual
# one-chunk device budget, each chunk staged H2D through the pinned-style
# staging pool — is SIGKILLed with staged buffers in flight, resumed, and
# the result must be BITWISE-identical to the in-HBM walk, with the
# donated-buffer device footprint staying O(chunk) and the staging-pool
# telemetry block journaled and validated by `obs_report --check`
python tests/_hostwalk_worker.py --smoke

# auto-fit kill-and-resume smoke (ISSUE 9/10): a journaled FUSED 3-order
# search (two d=0 orders batched into ONE group walk, then a d=1
# singleton) is SIGKILLed MID-GROUP — the fused walk torn with both
# orders' packed results partially durable — resumed, and the resumed
# selection must be BITWISE-identical to an uninterrupted fused search:
# per-group journals replay only uncommitted chunks, the demuxed
# selection argmin is recomputed from the full grid
python tests/_autofit_worker.py --smoke

# stepwise kill-and-resume smoke (ISSUE 19): the stepwise
# Hyndman–Khandakar search is SIGKILLed MID-EXPANSION — the 4-order seed
# pass fully durable, the expansion pass's fused walk torn after 2 of 3
# chunk commits — resumed, and the resumed search must replay the
# completed passes from their journals, recompute the IDENTICAL
# expansion, and select bitwise vs an uninterrupted stepwise run, with
# the per-pass manifest partitioning the trial walk
python tests/_autofit_worker.py --stepwise-smoke

# auto-fit tooling smoke (ISSUE 9/10): a short journaled FUSED order
# search with telemetry on must leave a group manifest carrying its grid
# coordinate + fusion membership, an auto_manifest.json that passes the
# obs_report schema gate, order-grid timeline lanes in the rendered
# report, and enough for the budget advisor to suggest orders_per_pass
# and the fusion width for the next search
AUTO_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu.models import auto

root = tempfile.mkdtemp(prefix="auto_smoke_")
rng = np.random.default_rng(0)
e = rng.normal(size=(24, 120)).astype(np.float32)
y = np.zeros_like(e)
for t in range(1, y.shape[1]):
    y[:, t] = 0.6 * y[:, t - 1] + e[:, t]
obs.enable(os.path.join(root, "events.jsonl"))
res = auto.auto_fit(y, [(1, 0, 0), (0, 0, 1)], chunk_rows=8, max_iters=20,
                    checkpoint_dir=os.path.join(root, "search"))
obs.disable()
am = res.meta["auto_fit"]
assert sum(am["selection_counts"].values()) == 24, am["selection_counts"]
assert am["compile_cache"]["hits"] is not None
assert am["diff_cache_hits"] == 1, am  # both orders share the d=0 prep
assert [g["orders"] for g in am["fusion_groups"]] == [[0, 1]], am
m = json.load(open(os.path.join(root, "search", "grid_00000",
                                "manifest.json")))
assert m["extra"]["grid"] == {"index": 0, "total": 2,
                              "fused": [0, 1]}, m["extra"]
assert m["extra"]["auto_fit"]["fused_orders"] == [0, 1]
assert m["extra"]["auto_fit"]["orders"] == [[1, 0, 0], [0, 0, 1]]
print(root)
EOF
)
python tools/obs_report.py --check "$AUTO_SMOKE_DIR/events.jsonl" \
  --manifest "$AUTO_SMOKE_DIR/search"
python tools/obs_report.py "$AUTO_SMOKE_DIR/events.jsonl" \
  | grep -q "order-grid lanes" \
  || { echo "ci.sh: obs_report did not render per-order lanes" >&2; exit 1; }
python tools/advise_budget.py "$AUTO_SMOKE_DIR/search" \
  | grep -q "orders_per_pass" \
  || { echo "ci.sh: advise_budget did not suggest orders_per_pass" >&2; exit 1; }
python tools/advise_budget.py "$AUTO_SMOKE_DIR/search" \
  | grep -q "fuse " \
  || { echo "ci.sh: advise_budget did not suggest a fusion width" >&2; exit 1; }
rm -rf "$AUTO_SMOKE_DIR"

# backtest kill-and-resume smoke (ISSUE 14): a journaled 3-window
# rolling-origin backtest campaign is SIGKILLed MID-CAMPAIGN — window 0's
# metrics durable, window 1's warm-started fit walk torn after its first
# chunk commits, window 2 unstarted — resumed, and the resumed campaign's
# per-window metric arrays (MAE/RMSE/MAPE/interval coverage) must be
# BITWISE-identical to an uninterrupted campaign: committed windows load
# their digest-verified metric shards, the torn window's fit journal
# replays only uncommitted chunks, forecasts recompute deterministically
python tests/_backtest_worker.py --smoke

# crash-mid-delta smoke (ISSUE 15): a delta walk — 3 chunks adopted
# byte-for-byte from a prior journal, 1 revised + 1 appended chunk
# computed — is SIGKILLed after 4 durable commits, resumed, and the
# resumed result must be BITWISE-identical to an uninterrupted delta walk
# AND to the from-scratch cold walk of the new panel, with the adopted
# chunks' manifest entries untouched by the resume (adopted chunks are
# never recomputed)
python tests/_delta_worker.py --smoke

# delta tooling smoke (ISSUE 15): a journaled delta refit with telemetry
# on must leave (a) a manifest whose extra.delta block passes the
# obs_report schema gate (class counts sum to the grid, adopted chunks
# name their source manifest), (b) an inspect_journal --delta dry-run
# that classifies a new panel against the prior journal, and (c) a
# dirty-fraction line + delta_from suggestion from the budget advisor
DELTA_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

root = tempfile.mkdtemp(prefix="delta_smoke_")
rng = np.random.default_rng(0)
e = rng.normal(size=(32, 96)).astype(np.float32)
y = np.zeros_like(e)
for t in range(1, y.shape[1]):
    y[:, t] = 0.6 * y[:, t - 1] + e[:, t]
kw = dict(chunk_rows=8, resilient=False, order=(1, 0, 0), max_iters=15)
rel.fit_chunked(arima.fit, y, checkpoint_dir=os.path.join(root, "full"), **kw)
y2 = y.copy(); y2[8:16] += 0.01
np.save(os.path.join(root, "y2.npy"), y2)
obs.enable(os.path.join(root, "events.jsonl"))
ref = rel.fit_chunked(arima.fit, y2, **kw)
d = rel.fit_chunked(arima.fit, y2, checkpoint_dir=os.path.join(root, "d"),
                    delta_from=os.path.join(root, "full"), **kw)
obs.disable()
for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
    np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(d, f)), err_msg=f)
assert d.meta["delta"]["counts"] == {"adopted": 3, "warm": 0, "dirty": 1,
                                     "new": 0}, d.meta["delta"]
m = json.load(open(os.path.join(root, "d", "manifest.json")))
assert m["extra"]["delta"]["counts"]["adopted"] == 3
print(root)
EOF
)
python tools/obs_report.py --check "$DELTA_SMOKE_DIR/events.jsonl" \
  --manifest "$DELTA_SMOKE_DIR/d"
python tools/inspect_journal.py "$DELTA_SMOKE_DIR/full" \
  --delta "$DELTA_SMOKE_DIR/y2.npy" \
  | grep -q "3 adopted" \
  || { echo "ci.sh: inspect_journal --delta did not classify the plan" >&2; exit 1; }
python tools/advise_budget.py "$DELTA_SMOKE_DIR/d" \
  | grep -q "dirty fraction" \
  || { echo "ci.sh: advise_budget did not report the dirty fraction" >&2; exit 1; }
python tools/advise_budget.py "$DELTA_SMOKE_DIR/full" \
  | grep -q "delta_from" \
  || { echo "ci.sh: advise_budget did not suggest delta_from" >&2; exit 1; }
rm -rf "$DELTA_SMOKE_DIR"

# forecast tooling smoke (ISSUE 14): a journaled panel forecast walk and
# a backtest campaign with telemetry on must leave (a) a forecast
# manifest whose extra.forecast block the budget advisor turns into
# horizon-aware chunk sizing, (b) a backtest_manifest.json that passes
# the obs_report schema gate (digest-verified metric shards, per-window
# fit journals), and (c) per-window campaign lanes in the rendered report
FORECAST_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import forecasting as fc, obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

root = tempfile.mkdtemp(prefix="forecast_smoke_")
rng = np.random.default_rng(0)
e = rng.normal(size=(16, 96)).astype(np.float32)
y = np.zeros_like(e)
for t in range(1, y.shape[1]):
    y[:, t] = 0.6 * y[:, t - 1] + e[:, t]
obs.enable(os.path.join(root, "events.jsonl"))
r = rel.fit_chunked(arima.fit, y, chunk_rows=8, resilient=False,
                    order=(1, 0, 0), max_iters=15,
                    checkpoint_dir=os.path.join(root, "fit"))
res = fc.forecast_chunked("arima", os.path.join(root, "fit"), y, 6,
                          model_kwargs={"order": (1, 0, 0)},
                          intervals=True, n_samples=32, chunk_rows=8,
                          checkpoint_dir=os.path.join(root, "fcj"))
bt = fc.run_backtest(y, "arima", 4, model_kwargs={"order": (1, 0, 0)},
                     fit_kwargs={"max_iters": 15}, n_windows=2,
                     chunk_rows=8, checkpoint_dir=os.path.join(root, "bt"))
obs.disable()
mem = fc.forecast_chunked("arima", r, y, 6,
                          model_kwargs={"order": (1, 0, 0)},
                          intervals=True, n_samples=32, chunk_rows=8)
for f in ("forecast", "lo", "hi"):
    np.testing.assert_array_equal(getattr(res, f), getattr(mem, f),
                                  err_msg=f)  # from-journal == from-memory
assert [w["status"] for w in bt.windows] == ["committed"] * 2, bt.windows
assert bt.windows[1]["warm_start"] is True, bt.windows
m = json.load(open(os.path.join(root, "fcj", "manifest.json")))
assert m["extra"]["forecast"]["horizon"] == 6, m["extra"]
print(root)
EOF
)
python tools/obs_report.py --check "$FORECAST_SMOKE_DIR/events.jsonl" \
  --manifest "$FORECAST_SMOKE_DIR/fcj"
python tools/obs_report.py --check "$FORECAST_SMOKE_DIR/events.jsonl" \
  --manifest "$FORECAST_SMOKE_DIR/bt"
python tools/obs_report.py "$FORECAST_SMOKE_DIR/events.jsonl" \
  | grep -q "backtest window lanes" \
  || { echo "ci.sh: obs_report did not render backtest window lanes" >&2; exit 1; }
python tools/advise_budget.py "$FORECAST_SMOKE_DIR/fcj" \
  | grep -q "horizon-aware chunk_rows" \
  || { echo "ci.sh: advise_budget did not suggest horizon-aware chunk_rows" >&2; exit 1; }
rm -rf "$FORECAST_SMOKE_DIR"

# sharded tooling smoke (ISSUE 6): a short journaled sharded walk with
# telemetry on must produce a merged manifest whose `shards` block passes
# the obs_report schema gate, render one timeline lane per shard, and give
# the budget advisor enough to suggest the shard count for the next run
SHARDED_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import arima

root = tempfile.mkdtemp(prefix="sharded_smoke_")
rng = np.random.default_rng(0)
y = np.cumsum(rng.normal(size=(32, 96)).astype(np.float32), axis=1)
obs.enable(os.path.join(root, "events.jsonl"))
res = rel.fit_chunked(arima.fit, y, chunk_rows=2, resilient=False,
                      order=(1, 0, 0), max_iters=15, shard=True,
                      checkpoint_dir=os.path.join(root, "journal"))
obs.disable()
assert res.meta["shards"]["n_shards"] == 8, res.meta["shards"]
m = json.load(open(os.path.join(root, "journal", "manifest.json")))
assert m["merged_from_shards"] == 8 and len(m["shards"]) == 8
assert all(c.get("shard_id") is not None for c in m["chunks"])
# per-lane overlap is a journaled fact, not just an in-memory meta dict
assert len(m["telemetry"]["shards_pipeline"]) == 8, \
    m["telemetry"].get("shards_pipeline")
print(root)
EOF
)
python tools/obs_report.py --check "$SHARDED_SMOKE_DIR/events.jsonl" \
  --manifest "$SHARDED_SMOKE_DIR/journal"
python tools/obs_report.py "$SHARDED_SMOKE_DIR/events.jsonl" \
  | grep -q "sharded lanes" \
  || { echo "ci.sh: obs_report did not render per-shard lanes" >&2; exit 1; }
python tools/advise_budget.py "$SHARDED_SMOKE_DIR/journal" \
  | grep -q "shards         =" \
  || { echo "ci.sh: advise_budget did not suggest a shard count" >&2; exit 1; }
rm -rf "$SHARDED_SMOKE_DIR"

# tick-loop kill-and-resume smoke (ISSUE 20): one cycle is SIGKILLed
# TWICE — first inside the delta-warm fit walk, then (after a resume
# from the recorded ticks) inside the publish walk with output shards
# already durable — and the second resume must finish the cycle and the
# next one bitwise-identical to an uninterrupted loop on a pristine copy
# of the data dir, with the twice-replayed append staying idempotent
python tests/_tickloop_worker.py --smoke

# streaming tooling smoke (ISSUE 20): a 2-cycle tick loop and a
# delta-adopting backtest campaign with telemetry on must (a) pass the
# obs_report schema gates — the tickloop root's stage/t_before chain +
# per-cycle published sink dirs, and the campaign manifest's
# window_class + delta block — and (b) give the budget advisor enough
# to print the across-cycle dirty fraction, a min_tick_interval_s
# feed-rate floor, and the delta=True adoption suggestion
TICK_SMOKE_DIR=$(python - <<'EOF'
import json, os, tempfile
import numpy as np
from spark_timeseries_tpu import obs
from spark_timeseries_tpu.forecasting import backtest as bt
from spark_timeseries_tpu.reliability import source as source_mod
from spark_timeseries_tpu.serving import tickloop as tl

root = tempfile.mkdtemp(prefix="tick_smoke_")
rng = np.random.default_rng(7)
y = np.empty((24, 64), np.float32)
y[:, 0] = rng.normal(size=24)
for t in range(1, 64):
    y[:, t] = 0.6 * y[:, t - 1] + 0.5 * rng.normal(size=24).astype(np.float32)
obs.enable(os.path.join(root, "events.jsonl"))
data = os.path.join(root, "data")
source_mod.write_npz_shards(data, y, 12)
loop = tl.TickLoop(os.path.join(root, "loop"), data, model="arima",
                   model_kwargs={"order": (1, 0, 0)},
                   fit_kwargs={"max_iters": 15}, horizon=4, chunk_rows=8,
                   seed=11)
for c in range(2):
    r = loop.run_cycle(0.1 * rng.normal(size=(24, 2)).astype(np.float32))
assert r.meta["stage"] == "published", r.meta
assert r.meta["delta_counts"]["adopted"] == 0, r.meta  # ticks dirty tails
kw = dict(model_kwargs={"order": (1, 0, 0)}, fit_kwargs={"max_iters": 15},
          chunk_rows=8)
bt.run_backtest(y[:, :60], "arima", 4, origins=[40, 48, 56],
                checkpoint_dir=os.path.join(root, "bt"), **kw)
d = bt.run_backtest(y, "arima", 4, origins=[40, 48, 56, 60], delta=True,
                    checkpoint_dir=os.path.join(root, "bt"), **kw)
obs.disable()
assert d.meta["delta"] == {**d.meta["delta"], "adopted": 3, "recomputed": 1}
print(root)
EOF
)
python tools/obs_report.py --check "$TICK_SMOKE_DIR/events.jsonl" \
  --manifest "$TICK_SMOKE_DIR/loop"
python tools/obs_report.py --check "$TICK_SMOKE_DIR/events.jsonl" \
  --manifest "$TICK_SMOKE_DIR/bt"
python tools/advise_budget.py "$TICK_SMOKE_DIR/loop" > /tmp/ci_tick_advise.txt
grep -q "dirty fraction" /tmp/ci_tick_advise.txt \
  || { echo "ci.sh: advise_budget did not report the tick-loop dirty fraction" >&2; exit 1; }
grep -q "min_tick_interval_s" /tmp/ci_tick_advise.txt \
  || { echo "ci.sh: advise_budget did not floor the feed rate" >&2; exit 1; }
python tools/advise_budget.py "$TICK_SMOKE_DIR/bt" \
  | grep -q "delta = True" \
  || { echo "ci.sh: advise_budget did not suggest backtest delta adoption" >&2; exit 1; }
rm -rf "$TICK_SMOKE_DIR"

# the driver's multi-chip artifact, same environment (now includes the
# sharded journaled chunk walk next to the SPMD mesh paths)
python - <<'EOF'
import __graft_entry__ as g
g.dryrun_multichip(8)
EOF
