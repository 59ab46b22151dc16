"""Precision tier: the program's ``rerank_sketch`` stage clock (the bottom
sketches of the documents in a candidate pair; span
``astpu.dedup.rerank.sketch``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_sketch")
