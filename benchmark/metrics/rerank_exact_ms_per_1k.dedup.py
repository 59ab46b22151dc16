"""Precision tier: the program's ``rerank_exact`` stage clock (every exact
shingle-Jaccard re-settle of a margin pair, both waves; span
``astpu.dedup.rerank.exact``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_exact")
