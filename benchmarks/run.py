"""Benchmark orchestrator — one module per paper figure/table.

Default (CI) mode runs the QUICK variants: every claim exercised end-to-end
on CPU in minutes. ``--full`` reproduces the complete grids used for
EXPERIMENTS.md (hours; run in the background). ``--only fig1`` selects one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SUMMARY_PATH = "experiments/BENCH_summary.json"
# Every registered benchmark, in run order — the suite dict in main() is
# checked against this so the --only help text can never go stale again.
SUITE_NAMES = ("fig1", "fig2", "fig3", "fig4", "fig5", "theorem1",
               "kernels", "roofline", "lowering", "engine_step", "serving")
# Where each bench leaves its committed record (None = prints only).
BENCH_FILES = {
    "fig1": "experiments/fig1.json",
    "fig2": "experiments/fig2.json",
    "fig3": "experiments/fig3.json",
    "fig4": "experiments/fig4.json",
    "fig5": "experiments/fig5.json",
    "theorem1": "experiments/theorem1.json",
    "engine_step": "experiments/BENCH_engine_step.json",
    "serving": "experiments/BENCH_serving.json",
}


def refresh_summary(name: str, timestamp: str, result=None,
                    out: str = SUMMARY_PATH) -> None:
    """After each registered bench: refresh the machine-readable perf
    trajectory — one headline entry per bench (speedups where the bench
    measures one) instead of scattered per-bench files. ``timestamp`` is
    passed in by the caller so one suite run shares one stamp."""
    headline: dict = {"ok": True}
    src = BENCH_FILES.get(name)
    if src and os.path.exists(src):
        headline["file"] = src
    if name == "engine_step":
        modes = (result or {}).get("modes")
        if modes is None and src and os.path.exists(src):
            with open(src) as f:
                modes = json.load(f).get("modes", {})
        if modes:
            speedups = {m: r["speedup"] for m, r in modes.items()}
            headline["speedups"] = speedups
            headline["min_speedup"] = min(speedups.values())
            # The compensated (EF top-k sparsified) stale-psum leg, tracked
            # alongside the dense speedups since PR 5.
            sparse = {m: r["sparse_speedup"] for m, r in modes.items()
                      if "sparse_speedup" in r}
            if sparse:
                headline["sparse_speedups"] = sparse
            # The one-pass fused-megakernel leg (PR 7): fused_donated /
            # mega_donated per mode.
            mega = {m: r["mega_speedup"] for m, r in modes.items()
                    if "mega_speedup" in r}
            if mega:
                headline["mega_speedups"] = mega
    if name == "serving":
        record = result
        if record is None and src and os.path.exists(src):
            with open(src) as f:
                record = json.load(f)
        record = record or {}
        sweep = record.get("sweep")
        # The serve-plane perf leg (PR 8): paged route vs the gather
        # reference, ratchet-guarded by check_floors' "serving" group.
        paged = record.get("paged") or {}
        if "paged_speedup" in paged:
            headline["paged_speedup"] = paged["paged_speedup"]
            headline["paged_tokens_per_s"] = paged["paged_tokens_per_s"]
        if sweep:
            # tokens/s headline next to the engine-step speedups, plus the
            # staleness span the refresh-period knob covered.
            best = max(sweep, key=lambda p: p["tokens_per_s"])
            headline["tokens_per_s"] = best["tokens_per_s"]
            headline["latency_p50_s"] = best["latency_p50_s"]
            headline["latency_p99_s"] = best["latency_p99_s"]
            stale = [p["staleness_mean_steps"] for p in sweep
                     if p["staleness_mean_steps"] is not None]
            if stale:
                headline["staleness_mean_steps_range"] = [min(stale),
                                                          max(stale)]
    data = {"benches": {}}
    if os.path.exists(out):
        try:
            with open(out) as f:
                data = json.load(f)
        except json.JSONDecodeError:
            pass
    data.setdefault("benches", {})[name] = {**headline, "at": timestamp}
    data["updated"] = timestamp
    with open(out, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + "|".join(SUITE_NAMES))
    args = ap.parse_args()
    quick = not args.full
    from repro import compile_cache
    compile_cache.enable()
    os.makedirs("experiments", exist_ok=True)

    from benchmarks import (fig1_depth_staleness, fig2_algorithms,
                            fig3_mf_lda_vae, fig4_coherence,
                            fig5_coherence_depth, kernels_bench,
                            theorem1_validation)

    def roofline():
        # Registered unconditionally so `--only roofline` never reports an
        # "unknown benchmark"; it needs the dry-run's output to do anything.
        if not os.path.exists("experiments/dryrun.jsonl"):
            print("roofline: SKIPPED — experiments/dryrun.jsonl not found; "
                  "generate it first with "
                  "`PYTHONPATH=src python -m repro.launch.dryrun`")
            return
        from benchmarks import roofline_report
        roofline_report.main()

    suite = {
        "fig1": lambda: fig1_depth_staleness.main(quick=quick,
                                                  out="experiments/fig1.json"),
        "fig2": lambda: fig2_algorithms.main(quick=quick,
                                             out="experiments/fig2.json"),
        "fig3": lambda: fig3_mf_lda_vae.main(quick=quick,
                                             out="experiments/fig3.json"),
        "fig4": lambda: fig4_coherence.main(quick=quick,
                                            out="experiments/fig4.json"),
        "fig5": lambda: fig5_coherence_depth.main(quick=quick,
                                                  out="experiments/fig5.json"),
        "theorem1": lambda: theorem1_validation.main(
            quick=quick, out="experiments/theorem1.json"),
        "kernels": kernels_bench.main,
        "roofline": roofline,
        "lowering": lambda: __import__(
            "benchmarks.lowering_bench", fromlist=["main"]).main(quick=quick),
        "engine_step": lambda: __import__(
            "benchmarks.engine_step_bench",
            fromlist=["main"]).main(quick=quick),
        "serving": lambda: __import__(
            "benchmarks.serving_bench", fromlist=["main"]).main(quick=quick),
    }

    assert tuple(suite) == SUITE_NAMES, "SUITE_NAMES out of sync with suite"
    # Validate the WHOLE --only list before running anything: a typo in the
    # second name used to surface only after the first benchmark had run for
    # minutes.
    names = args.only.split(",") if args.only else list(suite)
    unknown = [n for n in names if n not in suite]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown!r}; "
                         f"have {list(suite)}")
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    for name in names:
        t0 = time.time()
        print(f"\n===== {name} ({'full' if args.full else 'quick'}) =====",
              flush=True)
        ret = suite[name]()
        refresh_summary(name, stamp, result=ret if isinstance(ret, dict)
                        else None)
        print(f"===== {name} done in {time.time()-t0:.0f}s =====", flush=True)


if __name__ == "__main__":
    main()
