"""Dispatch executor: the program's ``h2d`` stage clock (the per-shard
puts of each tile group) over the window, per 1,000 articles, on the
sharded plane."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("h2d")
