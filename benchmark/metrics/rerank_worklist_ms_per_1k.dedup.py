"""Precision tier: the program's ``rerank_worklist`` stage clock (the
settlement work-list: coarse band pairs and every candidate cell; span
``astpu.dedup.rerank.worklist``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_worklist")
