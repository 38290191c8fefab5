"""Benchmark driver: one section per paper table/figure.

Prints ``name,value,derived`` CSV lines.  Sections:
  table5   -- row scan vs bitmap index (paper Table 5)
  table7   -- circuit gate counts vs Tables 6/7/8 (paper-faithfulness check)
  fig3     -- scaling with N and T (paper Figs 3/4)
  table10  -- workload ranking across algorithm families (paper 5.9)
  heatmap  -- SMALL-COMPETITIONS win/terrible rates (paper 5.8, App. C)
  weighted -- weighted thresholds: replication vs binary decomposition
  kernel   -- fused Pallas kernel traffic model + jnp wall-times
  query    -- unified query API: composed-circuit vs leafwise, batching,
              compiled-circuit cache (repro.query)
  stream   -- streaming update engine: delta apply + view refresh vs full
              rebuild, compaction amortization (repro.stream; smoke sizes)
  persist  -- on-disk format: snapshot size vs density, cold-load-to-
              first-query vs rebuild, WAL replay throughput (repro.persist;
              scratch snapshots in a temp dir, removed on exit)
  serve    -- multi-client serving front-end: coalesced QPS vs sequential
              across client counts, cache/dedup/shed rates, batch-size
              histogram, plan-memo + calibration counters (repro.serve;
              smoke sizes, writes BENCH_serve.json)
  obs      -- observability overhead: metrics+tracing ON vs OFF per-query
              cost, disabled-site cost, drift sample counts, Prometheus
              scrape lint (repro.obs; writes BENCH_obs.json and the
              BENCH_obs_trace.jsonl span-tree artifact)
  search   -- similarity search + windowed analytics: bitmap candidate
              generation raced vs the integer-list competitors (MergeOpt /
              DivideSkip / WHEAP) at the same T, adaptive top-k, window
              refresh words-touched vs the touched-tiles bound
              (repro.search; smoke sizes, writes BENCH_search.json)
  roofline -- three-term roofline per dry-run cell (deliverable g; requires
              artifacts/dryrun from ``python -m repro.launch.dryrun``)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sections = sys.argv[1:] or ["table5", "table7", "fig3", "table10", "heatmap", "kernel", "weighted", "query", "stream", "persist", "serve", "obs", "search", "roofline"]
    failures = 0
    for section in sections:
        print(f"# --- {section} ---")
        try:
            if section == "table5":
                from benchmarks import table5_rowscan as mod

                rows = mod.run()
            elif section == "table7":
                from benchmarks import table7_gates as mod

                rows = mod.run()
            elif section == "fig3":
                from benchmarks import fig3_scaling as mod

                rows = mod.run()
            elif section == "table10":
                from benchmarks import table10_workload as mod

                rows = mod.run()
            elif section == "kernel":
                from benchmarks import kernel_bench as mod

                rows = mod.run()
            elif section == "heatmap":
                from benchmarks import heatmap_competitions as mod

                rows = mod.run()
            elif section == "weighted":
                from benchmarks import weighted_bench as mod

                rows = mod.run()
            elif section == "query":
                from benchmarks import query_bench as mod

                rows = mod.run()
            elif section == "stream":
                from benchmarks import stream_bench as mod

                rows = mod.run(smoke=True)
            elif section == "persist":
                from benchmarks import persist_bench as mod

                rows = mod.run(smoke=True)
            elif section == "serve":
                from benchmarks import serve_bench as mod

                rows = mod.run(smoke=True)
            elif section == "obs":
                from benchmarks import obs_bench as mod

                rows = mod.run(smoke=True)
            elif section == "search":
                from benchmarks import search_bench as mod

                rows = mod.run(smoke=True)
            elif section == "roofline":
                from benchmarks import roofline as mod

                rows, table = mod.run()
                if table:
                    print(f"# roofline table -> {mod.write_markdown(table)}")
            else:
                raise ValueError(f"unknown section {section}")
            for name, val, extra in rows:
                print(f"{name},{val if isinstance(val, int) else round(float(val), 3)},{extra}")
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
