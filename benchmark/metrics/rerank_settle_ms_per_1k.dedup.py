"""Precision tier: the program's ``rerank_settle`` stage clock (the device
settle: pack, put, dispatch, finalize and its one readback; span
``astpu.dedup.rerank.settle``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_settle")
