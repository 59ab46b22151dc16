"""Precision tier: the program's ``rerank_cluster`` stage clock (union-
find, clusters, recall mass and the wave-2 pair classification; span
``astpu.dedup.rerank.cluster``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_cluster")
