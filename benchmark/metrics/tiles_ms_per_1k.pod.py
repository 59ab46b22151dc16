"""Sharded dispatch: the program's ``sharded_tiles`` clock (the whole
tile loop of ``dedup_reps_sharded``: chunking, per-shard packs and puts,
one partitioned dispatch per tile group) over the window, per 1,000
articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("sharded_tiles")
