"""One-shot resolve: the program's ``decisions`` stage clock (the result
counters and the per-document decision records; span
``astpu.dedup.decisions``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("decisions")
