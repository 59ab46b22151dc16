"""Precision tier: the program's ``rerank_readback`` stage clock (the
hook's readback of the signatures, candidate matrix and validity mask;
span ``astpu.dedup.rerank.readback``) over the window, per 1,000
articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("rerank_readback")
