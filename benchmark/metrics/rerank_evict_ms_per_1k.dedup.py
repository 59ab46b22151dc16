"""Precision tier: the program's ``rerank_evict`` stage clock (the
precision eviction walk, the edge list and the candidate rewrite; span
``astpu.dedup.rerank.evict``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_evict")
