#!/usr/bin/env python
"""Capture a local bench run as ``BENCH_LOCAL.json``.

Pipe a full ``bench.py`` run through this to record its output in the same
``{"tail": ...}`` shape as the driver's ``BENCH_r*.json`` artifacts, so
``tools/gen_readme_perf.py`` can regenerate the README table from
current-code numbers between driver rounds (provenance is labeled in the
generated table):

    python bench.py 2>&1 | python tools/save_local_bench.py

The artifact records its own run metadata — timestamp, git commit, and the
newest driver round present at run time — because file mtimes are not a
staleness signal (a fresh checkout gives every file one mtime; ADVICE r5):
``gen_readme_perf.py`` compares the RECORDED metadata, never ``st_mtime``.
"""

from __future__ import annotations

import json
import pathlib
import re
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def git_head() -> str | None:
    """Current commit hash, or None outside a usable git checkout."""
    try:
        out = subprocess.run(
            ["git", "-C", str(ROOT), "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def newest_driver_round() -> int:
    """Round number of the newest ``BENCH_r*.json`` present (0 if none)."""
    rounds = [
        int(m.group(1))
        for p in ROOT.glob("BENCH_r*.json")
        if (m := re.match(r"BENCH_r(\d+)\.json$", p.name))
    ]
    return max(rounds, default=0)


def main():
    text = sys.stdin.read()
    sys.stdout.write(text)  # pass through for the terminal
    # record the actual platform so the README provenance cannot claim TPU
    # numbers for a CPU run
    on_tpu = bool(re.search(r"platform=tpu", text))
    out = ROOT / "BENCH_LOCAL.json"
    now = time.time()
    out.write_text(json.dumps({
        "provenance": "local builder run (not a driver artifact)",
        "platform": "tpu" if on_tpu else "cpu-or-unknown",
        "cmd": "python bench.py",
        "run_at": now,
        "run_at_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(now)),
        "git_commit": git_head(),
        "newest_driver_round": newest_driver_round(),
        "tail": text[-8192:],
    }, indent=2) + "\n")
    print(f"[save_local_bench] wrote {out.name} (platform="
          f"{'tpu' if on_tpu else 'cpu-or-unknown'})", file=sys.stderr)


if __name__ == "__main__":
    main()
