"""Benchmark aggregator: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only accuracy,speedup,...]
                                          [--tune-env]

Writes machine-readable results to artifacts/bench/<name>.json alongside the
printed CSV-ish lines, plus ``BENCH_<name>.json`` files at the repo root
and a stable-schema ``BENCH_summary.json`` index (one entry per section:
headline metric, claim pass/fail, timestamp) so the perf trajectory is
tracked across PRs.

``--tune-env`` (opt-in, also ``BENCH_TUNE_ENV=1``) applies the
allocator/logging environment tuning common to JAX benchmark rigs —
tcmalloc via ``LD_PRELOAD`` when present on the system (re-execs the
process once to take effect), silenced TF logging, and no large-alloc
warnings.  Off by default: wall-clock numbers should be reproducible
with the environment the caller chose.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmarks import (
    accuracy, decode_attn, energy_breakdown, energy_comparison, faults,
    kv_quant, pairing_ablation, serve_throughput, speedup, traffic,
    vdpe_scaling,
)

SECTIONS = {
    "vdpe_scaling": vdpe_scaling.run,       # Fig. 4
    "energy_breakdown": energy_breakdown.run,  # Fig. 5
    "energy_comparison": energy_comparison.run,  # Fig. 6
    "speedup": speedup.run,                 # SIII speedup claim
    "pairing_ablation": pairing_ablation.run,  # beyond-paper: decorrelation study
    "accuracy": accuracy.run,               # SIII accuracy claim (trains a model)
    "serve_throughput": serve_throughput.run,  # ISSUE 1: fused vs per-step decode
    "kv_cache": serve_throughput.run_kv_cache,  # ISSUE 3: shared-prefix TTFT
    "scheduler": serve_throughput.run_scheduler,  # ISSUE 4: chunked-prefill ITL
    "decode_attn": decode_attn.run,         # ISSUE 5: gather-free paged decode
    "traffic": traffic.run_smoke,           # ISSUE 7: SLO-goodput vs load
    "kv_quant": kv_quant.run,               # ISSUE 8: int8 paged KV blocks
    "faults": faults.run_smoke,             # ISSUE 10: fault isolation/recovery
}

# the one number per section worth tracking across PRs (key into the
# section's result dict; sections without a scalar headline stay null)
HEADLINES = {
    "energy_comparison": "worst_accel_ratio",
    "speedup": "min_speedup_vs_best_accel",
    "accuracy": "worst_delta_pct",
    "serve_throughput": "min_fused_speedup_b8",
    "kv_cache": "best_ttft_speedup",
    "scheduler": "itl_improvement",
    "decode_attn": "speedup",
    "traffic": "peak_goodput_rps",
    "kv_quant": "capacity_ratio",
    "faults": "unaffected_identical_frac",
}

# allocator/logging environment applied by --tune-env (SNIPPETS.md 1-2
# idiom: tcmalloc preload + quiet TF + no large-alloc reports)
_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)
_TUNE_ENV = {
    "TF_CPP_MIN_LOG_LEVEL": "4",
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}


def maybe_tune_env(argv=None) -> None:
    """Apply the opt-in benchmark environment, re-execing once if a
    tcmalloc preload needs to take effect.  No-op unless ``--tune-env``
    or ``BENCH_TUNE_ENV=1`` is present, or if already applied."""
    argv = sys.argv if argv is None else argv
    want = "--tune-env" in argv or os.environ.get("BENCH_TUNE_ENV") == "1"
    if not want or os.environ.get("_BENCH_ENV_APPLIED") == "1":
        return
    os.environ.update(_TUNE_ENV)
    os.environ["_BENCH_ENV_APPLIED"] = "1"
    preload = os.environ.get("LD_PRELOAD", "")
    if "tcmalloc" not in preload:
        lib = next((p for p in _TCMALLOC_CANDIDATES if os.path.exists(p)), None)
        if lib is not None:
            os.environ["LD_PRELOAD"] = f"{preload} {lib}".strip()
            os.execv(sys.executable, [sys.executable] + argv)
    # no tcmalloc on the system (or already preloaded): the env vars
    # above still apply to this process


def _headline(name: str, result) -> dict:
    key = HEADLINES.get(name)
    value = None
    if key is not None and isinstance(result, dict):
        v = result.get(key)
        if isinstance(v, (int, float)):
            value = float(v)
    claim = result.get("claim_pass") if isinstance(result, dict) else None
    return {"headline_metric": key, "headline_value": value,
            "claim_pass": (bool(claim) if claim is not None else None)}


def main():
    maybe_tune_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset")
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--tune-env", action="store_true",
                    help="apply tcmalloc/TF-logging env tuning (opt-in)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    only = [s for s in args.only.split(",") if s]
    # reject typo'd section names loudly — a silently-empty run used to
    # look identical to an all-sections-skipped one
    from repro.launch.flags import check_choices

    check_choices(ap, "--only", only, list(SECTIONS))
    failures = []
    ran = {}
    for name, fn in SECTIONS.items():
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            result = fn()
            for path in (os.path.join(args.out, name + ".json"),
                         os.path.join(REPO_ROOT, f"BENCH_{name}.json")):
                with open(path, "w") as f:
                    json.dump(result, f, indent=2, default=float)
            ran[name] = result
            if isinstance(result, dict) and result.get("claim_pass") is False:
                failures.append(name)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"({name}: {time.time() - t0:.1f}s)", flush=True)
    print("\n===== summary =====")
    print("benchmarks,failures," + (";".join(failures) if failures else "none"))
    # merge into the existing index so `--only` runs don't erase the other
    # sections' entries from the cross-PR trajectory.  Schema per section
    # (stable across PRs): name, headline_metric, headline_value,
    # claim_pass (null when the section states no claim), unix_time,
    # failed.
    summary_path = os.path.join(REPO_ROOT, "BENCH_summary.json")
    sections: dict = {}
    if os.path.exists(summary_path):
        try:
            with open(summary_path) as f:
                sections = json.load(f).get("sections", {})
        except (json.JSONDecodeError, AttributeError):
            sections = {}
    # upgrade pre-schema entries in place so every section has the keys
    for name, entry in sections.items():
        sections[name] = {
            "name": name, "headline_metric": HEADLINES.get(name),
            "headline_value": None, "claim_pass": None,
            "unix_time": None, "failed": None, **entry}
    now = time.time()
    for name, result in ran.items():
        sections[name] = {"name": name, **_headline(name, result),
                          "unix_time": now, "failed": name in failures}
    for name in failures:
        sections.setdefault(name, {
            "name": name, "headline_metric": HEADLINES.get(name),
            "headline_value": None, "claim_pass": None,
            "unix_time": now, "failed": True})
    with open(summary_path, "w") as f:
        json.dump({"schema_version": 1, "sections": sections,
                   "last_failures": failures}, f, indent=2)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
