"""Benchmark harness entry point — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, one
row per headline metric of each benchmark, then a human-readable summary.

  python -m benchmarks.run [--quick]
  python -m benchmarks.run --check-mirrors   # no benches; verify repo-root
                                             # BENCH_*.json mirrors match
                                             # benchmarks/results/
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _csv(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def _scalars(d: dict) -> dict:
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float, bool, str))}


def _mirror(name: str, us_per_call: float, result: dict) -> None:
    """Mirror a benchmark's headline (scalar) metrics to a repo-root
    ``BENCH_<name>.json`` — the full row-level results stay under
    ``benchmarks/results/``; the root copy is the at-a-glance summary
    (file names match the CSV row names)."""
    payload = {"benchmark": name, "us_per_call": us_per_call,
               **_scalars(result)}
    with open(os.path.join(ROOT, f"BENCH_{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")


def check_mirrors() -> int:
    """Verify every ``benchmarks/results/BENCH_<name>.json`` has a repo-root
    mirror whose scalar metrics match it exactly.

    The two copies are written from the same in-memory result dict (the
    bench module writes results/, ``_mirror`` writes the root summary), so
    any divergence means one side was regenerated without the other —
    exactly the drift this check exists to catch.  Returns a process exit
    code (0 = consistent).
    """
    results_dir = os.path.join(ROOT, "benchmarks", "results")
    problems: list[str] = []
    checked = 0
    for fn in sorted(os.listdir(results_dir)):
        if not (fn.startswith("BENCH_") and fn.endswith(".json")):
            continue
        checked += 1
        root_path = os.path.join(ROOT, fn)
        if not os.path.exists(root_path):
            problems.append(f"{fn}: repo-root mirror missing")
            continue
        with open(os.path.join(results_dir, fn)) as f:
            full = _scalars(json.load(f))
        with open(root_path) as f:
            mirror = json.load(f)
        missing = sorted(k for k in full if k not in mirror)
        drifted = sorted(k for k in full if k in mirror and mirror[k] != full[k])
        if missing:
            problems.append(f"{fn}: mirror missing keys {missing}")
        if drifted:
            for k in drifted:
                problems.append(
                    f"{fn}: {k} results={full[k]!r} mirror={mirror[k]!r}")
    if problems:
        for p in problems:
            print(f"MIRROR DRIFT {p}", file=sys.stderr)
        return 1
    print(f"# mirrors consistent: {checked} results files checked")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sample sizes (CI mode)")
    ap.add_argument("--check-mirrors", action="store_true",
                    help="only verify repo-root BENCH_*.json mirrors match "
                         "benchmarks/results/; run no benchmarks")
    args = ap.parse_args()
    if args.check_mirrors:
        sys.exit(check_mirrors())
    quick = args.quick
    from repro.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    print("name,us_per_call,derived")
    t_all = time.perf_counter()

    # ---- Table 1: expert calibration --------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_table1_calibration
    r = bench_table1_calibration.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    _csv("table1_calibration", dt,
         f"mean_expert_ece_change_pct={r['mean_expert_ece_change_pct']:.1f};"
         f"ensemble_ece_change_pct={r['ensemble_ece_change_pct']:.1f};"
         f"paper=-80_to_-98_and_-90.8")
    _mirror("table1_calibration", dt, r)

    # ---- Fig. 4: quantile transformation update ---------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_fig4_quantile_update
    r = bench_fig4_quantile_update.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    _csv("fig4_quantile_update", dt,
         f"raw_mass_first_bin={r['raw_mass_in_first_bin']:.3f};"
         f"v0_max_high_bin_err={r['v0_max_abs_rel_err_high_bins']:.2f};"
         f"v1_max_mid_bin_err={r['v1_max_abs_rel_err_mid_bins']:.3f}")
    _mirror("fig4_quantile_update", dt, r)

    # ---- fleet-wide atomic calibration refresh (separate timing row) -------
    rr = bench_fig4_quantile_update.run_refresh(quick=quick)
    _csv("fig4_fleet_refresh", rr["wall_ms_at_max"] * 1e3,
         f"tenants={rr['max_tenants']};"
         f"us_per_tenant={rr['us_per_tenant_at_max']:.1f};"
         f"atomic_generations={rr['rows'][-1]['generation']}")
    _mirror("fig4_fleet_refresh", rr["wall_ms_at_max"] * 1e3, rr)

    # ---- Fig. 6: live model update -----------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_fig6_model_update
    r = bench_fig6_model_update.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    _csv("fig6_model_update", dt,
         f"recall_p1={r['recall_p1']:.4f};recall_p2={r['recall_p2']:.4f};"
         f"monotone_recall_invariant={abs(r['recall_p1.5'] - r['recall_p2']) < 1e-9};"
         f"p15_max_err={r['p15_max_abs_err']:.2f};p2_max_err={r['p2_max_abs_err']:.2f};"
         f"alert_rate_p15={r['alert_rate_p1.5']:.4f};"
         f"alert_rate_p2={r['alert_rate_p2']:.4f};psi_p2={r['psi_p2']:.3f}")
    _mirror("fig6_model_update", dt, r)

    # ---- Fig. 5: rollout stability -----------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_fig5_rollout
    r = bench_fig5_rollout.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    _csv("fig5_rollout", dt,
         f"pod_peak={r['pod_peak']};min_ready={r['min_ready']};"
         f"p99_latency_ms={r['latency_p99_ms']:.2f};"
         f"final_version={r['final_version']}")
    _mirror("fig5_rollout", dt, r)

    # ---- Appendix A: sample-size bound -------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_appendix_a
    r = bench_appendix_a.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    worst = min(row["coverage_at_n"] for row in r["rows"])
    _csv("appendix_a_samplesize", dt,
         f"worst_coverage_at_n={worst:.3f};nominal=0.95;"
         f"rows={len(r['rows'])}")
    _mirror("appendix_a_samplesize", dt,
            {**r, "worst_coverage_at_n": worst, "nominal": 0.95})

    # ---- serving latency/throughput ----------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_serving_latency
    r = bench_serving_latency.run(quick=quick)
    dt = (time.perf_counter() - t0) * 1e6
    _csv("serving_latency", r["batch_1"]["latency_ms"] * 1e3,
         f"events_per_s_b256={r['batch_256']['events_per_s']:.0f};"
         f"transform_share_pct={r['transform_share_of_path_pct']:.2f}")
    _mirror("serving_latency", r["batch_1"]["latency_ms"] * 1e3,
            {**r, "latency_ms_b1": r["batch_1"]["latency_ms"],
             "events_per_s_b256": r["batch_256"]["events_per_s"]})

    # ---- mixed-tenant banked batch vs per-predictor loop --------------------
    from benchmarks import bench_multitenant_batch
    r = bench_multitenant_batch.run(quick=quick)
    _csv("multitenant_batch", r["us_banked"],
         f"kernel_speedup={r['kernel_speedup']:.1f}x;"
         f"events_per_s_banked={r['events_per_s_banked']:.0f};"
         f"quantile_update_speedup={r['quantile_update_speedup']:.1f}x;"
         f"max_abs_err={r['max_abs_err_vs_oracle']:.2e}")
    _mirror("multitenant_batch", r["us_banked"], r)

    # ---- tenant-sharded banks: per-shard residency + dispatch throughput ----
    from benchmarks import bench_sharded_bank
    r = bench_sharded_bank.run(quick=quick)
    _csv("sharded_bank", r["us_per_batch_smax"],
         f"tenants={r['max_tenants']};shards={r['max_shards']};"
         f"residency_ratio={r['residency_ratio_at_smax']:.3f};"
         f"throughput_ratio_s1={r['throughput_ratio_s1']:.2f}x;"
         f"bitwise_parity={r['all_bitwise_parity']}")
    _mirror("sharded_bank", r["us_per_batch_smax"], r)

    # ---- tiered bank store: bounded device residency + hot-path throughput --
    from benchmarks import bench_tiered_bank
    r = bench_tiered_bank.run(quick=quick)
    _csv("tiered_bank", r["us_per_batch_hot_at_max"],
         f"tenants={r['max_tenants']};"
         f"device_kb={r['device_bytes'] / 1024:.0f};"
         f"device_bytes_bounded={r['device_bytes_bounded']};"
         f"hot_events_per_s={r['events_per_s_hot_at_max']:.0f};"
         f"hot_vs_s8={r['hot_vs_s8_ratio']:.2f}x;"
         f"stall_rate_mixed={r['stall_rate_mixed_at_max']:.4f};"
         f"stall_rate_prefetched={r['stall_rate_prefetched_at_max']:.4f};"
         f"p99_ms_locked_staging={r['p99_ms_dispatch_locked_staging']:.2f};"
         f"p99_ms_overlap_staging={r['p99_ms_dispatch_overlap_staging']:.2f};"
         f"stall_fix_p99_speedup={r['stall_fix_p99_speedup']:.2f}x;"
         f"bitwise_parity={r['bitwise_parity']}")
    _mirror("tiered_bank", r["us_per_batch_hot_at_max"], r)

    # ---- fleet calibration: merged-fit + fenced broadcast vs fleet size -----
    from benchmarks import bench_fleet_refresh
    r = bench_fleet_refresh.run(quick=quick)
    _csv("fleet_refresh", r["wall_ms_at_max"] * 1e3,
         f"replicas={r['max_replicas']};streams={r['tenants']};"
         f"merge_ms={r['merge_ms_at_max']:.1f};"
         f"publish_ms={r['publish_ms_at_max']:.1f};"
         f"refit_ratio_max_vs_min={r['refit_ratio_max_vs_min']:.2f};"
         f"all_within_bound={r['all_within_bound']}")
    _mirror("fleet_refresh", r["wall_ms_at_max"] * 1e3, r)

    # ---- adversarial campaign: dispatch latency with full client stack on --
    from benchmarks import bench_attack_campaign
    r = bench_attack_campaign.run(quick=quick)
    _csv("attack_campaign", r["us_per_event_attack"],
         f"p99_quiet_ms={r['p99_ms_quiet']:.2f};"
         f"p99_attack_ms={r['p99_ms_attack']:.2f};"
         f"p99_ratio={r['p99_ratio_attack_vs_quiet']:.2f};"
         f"audit_us_per_event={r['audit_us_per_event']:.2f};"
         f"attack_refreshes={r['attack_refreshes']}")
    _mirror("attack_campaign", r["us_per_event_attack"], r)

    # ---- async banked dispatch engine vs synchronous ServerBatcher ----------
    from benchmarks import bench_async_engine
    r = bench_async_engine.run(quick=quick)
    _csv("async_engine", r["us_per_event_async"],
         f"speedup={r['speedup_vs_sync']:.2f}x;"
         f"speedup_fixed_windows={r['speedup_fixed_vs_sync']:.2f}x;"
         f"events_per_s_async={r['events_per_s_async']:.0f};"
         f"events_per_s_sync={r['events_per_s_sync']:.0f};"
         f"tracking_on_off_ratio={r['tracking_on_off_ratio']:.2f};"
         f"events_per_s_track_on={r['events_per_s_track_on']:.0f};"
         f"events_per_s_track_off={r['events_per_s_track_off']:.0f};"
         f"tenants={r['tenants']};max_abs_err={r['max_abs_err']:.2e}")
    _mirror("async_engine", r["us_per_event_async"], r)

    # ---- kernels -------------------------------------------------------------
    t0 = time.perf_counter()
    from benchmarks import bench_kernels
    r = bench_kernels.run(quick=quick)
    for name, row in r.items():
        derived = f"allclose={row.get('kernel_allclose', True)}"
        if "skip_rate_sorted" in row:
            derived += (f";skip_rate_sorted={row['skip_rate_sorted']:.2f}"
                        f";skip_rate_adversarial="
                        f"{row['skip_rate_adversarial']:.2f}")
        _csv(f"kernel_{name}", row["us_per_call"], derived)
    with open(os.path.join(ROOT, "BENCH_kernels.json"), "w") as f:
        json.dump({"benchmark": "kernels",
                   **{name: _scalars(row) for name, row in r.items()}},
                  f, indent=1, sort_keys=True)
        f.write("\n")

    print(f"\n# total bench time: {time.perf_counter() - t_all:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
