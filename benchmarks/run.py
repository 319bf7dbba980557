"""Benchmark driver — one benchmark per paper table/figure/§6 factor.
Prints ``name,us_per_call,derived`` CSV. Roofline tables (the LM perf
report) are produced separately by ``python -m benchmarks.roofline`` from
the dry-run artifacts."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (bench_apps, bench_autoscale, bench_broker, bench_core,
                   bench_federation, bench_obs, bench_pipeline,
                   bench_preemption, bench_recovery, bench_routing,
                   bench_serve)

    suites = [
        ("broker_data_plane", bench_broker.bench_broker_data_plane),
        ("broker_throughput", bench_core.bench_broker_throughput),
        ("submit_latency", bench_core.bench_submit_latency),
        ("oversubscription_vs_celery",
         bench_core.bench_oversubscription_vs_celery),
        ("startup_sync", bench_core.bench_startup_sync),
        ("failure_recovery", bench_core.bench_failure_recovery),
        ("resource_routing", bench_routing.bench_resource_routing),
        ("fair_share", bench_routing.bench_fair_share),
        ("writhe_kernel", bench_apps.bench_writhe_kernel),
        ("knot_campaign", bench_apps.bench_knot_campaign),
        ("pipeline_vs_flat", bench_pipeline.bench_pipeline_vs_flat),
        ("pipeline_orchestration_overhead",
         bench_pipeline.bench_pipeline_orchestration_overhead),
        ("journal_overhead", bench_recovery.bench_journal_overhead),
        ("recovery_time", bench_recovery.bench_recovery_time),
        ("autoscale_burst", bench_autoscale.bench_autoscale_burst),
        ("federation", bench_federation.bench_federation),
        ("preemption", bench_preemption.bench_preemption),
        ("obs_overhead", bench_obs.bench_obs_overhead),
        ("train_step", bench_apps.bench_train_step),
        ("serve_continuous_batching",
         bench_apps.bench_serve_continuous_batching),
        ("serve_tier", bench_serve.bench_serve),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.2f},\"{derived}\"", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},NaN,\"ERROR\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
