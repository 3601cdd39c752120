"""Subprocess worker for the SHARDED kill-and-resume durability tests
(ISSUE 6).

Runs a journaled sharded chunk walk (8 forced CPU devices, one lane per
device, 2 chunks per lane) of a deterministic AR(1) panel, optionally
SIGKILLing itself after N durable chunk commits — a real process death
landing while several lanes are mid-walk, exactly a multi-chip preemption.
The resumed run must replay ONLY the shard chunks that did not commit and
end bitwise-identical to an uninterrupted sharded run AND to the
single-device walk of the same panel, with exactly ONE merged job
manifest at the journal root.

Modes:
    --run --dir D [--kill-after N] [--single] [--lane-kill S] [--out F]
        one journaled walk (sharded unless --single); with --kill-after
        the process dies mid-job (exit by SIGKILL), else the assembled
        result is saved to F.  --lane-kill S permanently fails lane S's
        fit calls after its first chunk (ISSUE 11): the elastic
        supervisor must retry, quarantine it, and finish on survivors.
    --smoke
        full orchestration (used by ci.sh and tests/test_sharded.py):
        SIGKILL a sharded walk after 5 commits, verify it died with only
        shard-namespace manifests on disk, resume, compare bitwise
        against an uninterrupted sharded run AND a single-device run,
        and assert the resumed journal holds exactly one merged root
        manifest accounting for every chunk.
    --elastic-smoke
        elastic orchestration (ISSUE 11, used by ci.sh and
        tests/test_elastic.py): (1) a sharded walk with lane 2 killed
        mid-job completes on the survivors, bitwise-identical to the
        uninterrupted single-device walk, with the quarantine and the
        reassigned chunks recorded in the merged manifest; (2) the SAME
        degraded job is then SIGKILLed mid-rebalance and resumed with
        the lane healthy again — the resume re-admits the previously
        quarantined device, adopts every durable chunk regardless of
        which lane's namespace holds it, and is again bitwise-identical
        to the single-device walk.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

CHUNK_ROWS = 2
N_ROWS = 32  # 16 chunks over 8 lanes: every lane walks 2 chunks


def make_panel() -> np.ndarray:
    rng = np.random.default_rng(11)
    e = rng.normal(size=(N_ROWS, 96)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, y.shape[1]):
        y[:, i] = 0.6 * y[:, i - 1] + e[:, i]
    return y


def run_fit(directory: str, kill_after: int | None, single: bool,
            out: str | None, lane_kill: int | None = None) -> None:
    from spark_timeseries_tpu import reliability as rel
    from spark_timeseries_tpu.models import arima
    from spark_timeseries_tpu.reliability import faultinject as fi
    from spark_timeseries_tpu.utils import compile_cache

    # amortize the 8-device compiles across the smoke's worker processes
    compile_cache.configure()

    hook = None
    if kill_after is not None:
        hook = fi.kill_after_commits(kill_after)
    fit_fn = arima.fit
    if lane_kill is not None:
        # permanent lane death after its first chunk: the retries fail
        # too, so the elastic supervisor must quarantine the lane and
        # finish the job on the survivors (ISSUE 11)
        fit_fn = fi.lane_kill(arima.fit, lane_kill, after_chunks=1)
    res = rel.fit_chunked(
        fit_fn, make_panel(), chunk_rows=CHUNK_ROWS, resilient=False,
        checkpoint_dir=directory, order=(1, 0, 0), max_iters=25,
        shard=not single, _journal_commit_hook=hook,
    )
    if kill_after is not None:
        sys.exit(f"kill_after={kill_after} but the fit finished — the hook "
                 "never fired")
    if out:
        elastic = (res.meta.get("shards") or {}).get("elastic") or {}
        np.savez(out, params=res.params, nll=res.neg_log_likelihood,
                 converged=res.converged, iters=res.iters, status=res.status,
                 journal=json.dumps(res.meta.get("journal", {})),
                 elastic=json.dumps(elastic))


def _child(args: list) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ},
        capture_output=True, text=True, timeout=900,
    )


def smoke() -> None:
    with tempfile.TemporaryDirectory() as td:
        jdir = os.path.join(td, "journal")
        # 1. sharded walk killed by SIGKILL after 5 durable commits (of 16)
        r = _child(["--run", "--dir", jdir, "--kill-after", "5"])
        if r.returncode != -9:
            sys.exit(f"expected SIGKILL (-9), got rc={r.returncode}\n"
                     f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
        if os.path.exists(os.path.join(jdir, "manifest.json")):
            sys.exit("killed mid-job but a root manifest exists — the merge "
                     "must only run after the lanes join")
        shard_manifests = glob.glob(
            os.path.join(jdir, "shard_*", "manifest.shard_*.json"))
        if not shard_manifests:
            sys.exit("no shard-namespace manifests after the kill — the "
                     "lanes never journaled")
        committed0 = 0
        for mp in shard_manifests:
            m = json.load(open(mp))
            committed0 += sum(1 for c in m["chunks"]
                              if c["status"] == "committed")
        if committed0 < 5:
            sys.exit(f"expected >= 5 durable chunks at the kill, "
                     f"found {committed0}")
        # 2. sharded resume completes the job, replaying only the rest
        resumed_out = os.path.join(td, "resumed.npz")
        r = _child(["--run", "--dir", jdir, "--out", resumed_out])
        if r.returncode != 0:
            sys.exit(f"resume failed rc={r.returncode}\nstderr:\n{r.stderr}")
        # 3. uninterrupted sharded reference in a fresh directory
        full_out = os.path.join(td, "full.npz")
        r = _child(["--run", "--dir", os.path.join(td, "fresh"), "--out",
                    full_out])
        if r.returncode != 0:
            sys.exit(f"sharded reference failed rc={r.returncode}\n{r.stderr}")
        # 4. single-device walk of the same panel (the identity bar)
        single_out = os.path.join(td, "single.npz")
        r = _child(["--run", "--dir", os.path.join(td, "single"), "--single",
                    "--out", single_out])
        if r.returncode != 0:
            sys.exit(f"single-device run failed rc={r.returncode}\n{r.stderr}")
        a = np.load(resumed_out)
        for name, path in (("uninterrupted sharded", full_out),
                           ("single-device", single_out)):
            b = np.load(path)
            for k in ("params", "nll", "converged", "iters", "status"):
                if not np.array_equal(a[k], b[k], equal_nan=True):
                    sys.exit(f"resumed sharded result differs from the "
                             f"{name} run on {k!r} — NOT bitwise-identical")
        j = json.loads(str(a["journal"]))
        n_chunks = N_ROWS // CHUNK_ROWS
        if j.get("chunks_resumed", 0) < committed0:
            sys.exit(f"resume replayed fewer chunks than were durable at "
                     f"the kill: {j}")
        if j.get("chunks_committed") != n_chunks or j.get("merged_shards") != 8:
            sys.exit(f"merged accounting wrong: {j}")
        # 5. exactly ONE merged job manifest, written at the root
        roots = glob.glob(os.path.join(jdir, "**", "manifest.json"),
                          recursive=True)
        if roots != [os.path.join(jdir, "manifest.json")]:
            sys.exit(f"expected exactly one root manifest.json, got {roots}")
        m = json.load(open(roots[0]))
        if m.get("merged_from_shards") != 8 or len(m.get("shards", [])) != 8:
            sys.exit(f"root manifest is not the 8-shard merge: "
                     f"{ {k: m.get(k) for k in ('merged_from_shards',)} }")
        done = sum(1 for c in m["chunks"] if c["status"] == "committed")
        if done != n_chunks:
            sys.exit(f"merged manifest should show {n_chunks} committed "
                     f"chunks, got {done}")
        print("sharded kill-and-resume smoke: PASS "
              f"(SIGKILL after {committed0} durable commits, resumed "
              f"replayed only the remaining {n_chunks - committed0} chunks "
              "bitwise-identical to the uninterrupted sharded AND "
              "single-device walks, one merged manifest)")


def elastic_smoke() -> None:
    with tempfile.TemporaryDirectory() as td:
        n_chunks = N_ROWS // CHUNK_ROWS
        # 0. the identity bar: uninterrupted single-device walk
        single_out = os.path.join(td, "single.npz")
        r = _child(["--run", "--dir", os.path.join(td, "single"), "--single",
                    "--out", single_out])
        if r.returncode != 0:
            sys.exit(f"single-device run failed rc={r.returncode}\n{r.stderr}")
        ref = np.load(single_out)

        # 1. lane 2 dies mid-job: the job must COMPLETE on survivors,
        # bitwise vs the single-device walk, quarantine journaled
        jdir = os.path.join(td, "degraded")
        deg_out = os.path.join(td, "degraded.npz")
        r = _child(["--run", "--dir", jdir, "--lane-kill", "2",
                    "--out", deg_out])
        if r.returncode != 0:
            sys.exit(f"lane-killed job should survive on the other 7 lanes, "
                     f"got rc={r.returncode}\nstderr:\n{r.stderr}")
        a = np.load(deg_out)
        for k in ("params", "nll", "converged", "iters", "status"):
            if not np.array_equal(a[k], ref[k], equal_nan=True):
                sys.exit(f"degraded result differs from single-device on "
                         f"{k!r} — NOT bitwise-identical")
        el = json.loads(str(a["elastic"]))
        if [q["shard_id"] for q in el.get("quarantined", [])] != [2]:
            sys.exit(f"expected lane 2 quarantined, got {el}")
        m = json.load(open(os.path.join(jdir, "manifest.json")))
        rb = m.get("rebalance") or {}
        if [q["shard_id"] for q in rb.get("quarantined", [])] != [2]:
            sys.exit(f"merged manifest's rebalance block wrong: {rb}")
        done = sum(1 for c in m["chunks"] if c["status"] == "committed")
        if done != n_chunks:
            sys.exit(f"degraded job committed {done}/{n_chunks} chunks")
        if not all(isinstance(c.get("owner"), int) for c in m["chunks"]):
            sys.exit("merged chunk entries are missing owner tags")
        if rb.get("reassigned_chunks", 0) < 1:
            sys.exit(f"expected reassigned chunks in the manifest: {rb}")

        # 2. the SAME degraded job, SIGKILLed mid-rebalance, then resumed
        # with lane 2 healthy: quarantine must compose with crash-resume,
        # and the resume must re-admit the quarantined device and adopt
        # chunks from every namespace
        jdir2 = os.path.join(td, "killed")
        r = _child(["--run", "--dir", jdir2, "--lane-kill", "2",
                    "--kill-after", "6"])
        if r.returncode != -9:
            sys.exit(f"expected SIGKILL (-9), got rc={r.returncode}\n"
                     f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
        if os.path.exists(os.path.join(jdir2, "manifest.json")):
            sys.exit("killed mid-job but a root manifest exists")
        committed0 = sum(
            sum(1 for c in json.load(open(mp))["chunks"]
                if c["status"] == "committed")
            for mp in glob.glob(os.path.join(jdir2, "shard_*",
                                             "manifest.shard_*.json")))
        if committed0 < 6:
            sys.exit(f"expected >= 6 durable chunks at the kill, "
                     f"found {committed0}")
        resumed_out = os.path.join(td, "resumed.npz")
        r = _child(["--run", "--dir", jdir2, "--out", resumed_out])
        if r.returncode != 0:
            sys.exit(f"resume failed rc={r.returncode}\nstderr:\n{r.stderr}")
        a = np.load(resumed_out)
        for k in ("params", "nll", "converged", "iters", "status"):
            if not np.array_equal(a[k], ref[k], equal_nan=True):
                sys.exit(f"resumed rebalanced result differs from "
                         f"single-device on {k!r} — NOT bitwise-identical")
        el = json.loads(str(a["elastic"]))
        if el.get("quarantined"):
            sys.exit(f"healthy resume must re-admit the quarantined lane, "
                     f"got {el}")
        j = json.loads(str(a["journal"]))
        if j.get("chunks_resumed", 0) < committed0:
            sys.exit(f"resume replayed fewer chunks than were durable at "
                     f"the kill ({committed0}): {j}")
        if j.get("chunks_committed") != n_chunks or j.get("merged_shards") != 8:
            sys.exit(f"merged accounting wrong: {j}")
        print("elastic lane smoke: PASS "
              f"(lane 2 quarantined mid-job, survivors finished all "
              f"{n_chunks} chunks bitwise-identical to the single-device "
              f"walk with {rb.get('reassigned_chunks')} reassigned; the "
              f"SIGKILLed degraded job resumed bitwise with "
              f"{j.get('chunks_resumed')} durable chunks adopted)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--elastic-smoke", action="store_true")
    ap.add_argument("--dir")
    ap.add_argument("--kill-after", type=int, default=None)
    ap.add_argument("--lane-kill", type=int, default=None)
    ap.add_argument("--single", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    if args.smoke:
        return smoke()
    if args.elastic_smoke:
        return elastic_smoke()
    if not args.run or not args.dir:
        ap.error("need --run --dir D, --smoke, or --elastic-smoke")
    run_fit(args.dir, args.kill_after, args.single, args.out, args.lane_kill)


if __name__ == "__main__":
    main()
