"""The benchmark: the yardstick later PRs are measured with and may not edit.

`BENCHMARK.json` at the repo root names the cells; everything they need is a
file in this directory found by name (configs/, traffic/, metrics/, ops/,
readers/, refs/). PERF.md says how a PR adds each.
"""
