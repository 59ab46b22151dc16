"""Matcher row prep: the program's ``matcher_rows`` stage clock (the frame-
to-row loop of ``match_chunk_async``, date parsing included; span
``astpu.matcher.rows``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("matcher_rows")
