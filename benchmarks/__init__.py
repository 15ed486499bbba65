"""Benchmark + load-test harnesses (reference SURVEY.md §4 parity).

  benchmarks/micro.py — the `make benchmark` analog: ingest push rate,
      WAL append per codec, block write/read per codec, search under
      concurrent write load, compaction throughput. Each prints a JSON
      line; `python -m benchmarks.micro` runs all.
  benchmarks/load.py — the k6 smoke/stress analog: staged virtual users
      driving the real HTTP API (in-process single binary by default, or
      --url for a running cluster), with latency thresholds.

The benchmark the driver runs on the chip is `chipbench/`
(`BENCHMARK.json`); these two are CPU-side harnesses (ROADMAP De4).
"""
