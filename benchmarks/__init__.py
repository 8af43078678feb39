"""Benchmark harness — one module per paper table/figure.

  bench_accuracy   — Table II   (final accuracy under severe label skew)
  bench_comm       — Table III  (communication overhead, MB)
  bench_rounds     — Fig 3      (rounds-to-target-accuracy, −22% claim)
  roofline         — EXPERIMENTS.md §Roofline from results/dryrun.jsonl

``python -m benchmarks.run`` executes all of them and prints
``name,us_per_call,derived`` CSV rows.
"""
