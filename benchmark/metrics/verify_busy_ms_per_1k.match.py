"""Verify pool: the program's ``matcher_verify_busy`` stage clock (seconds
the verify workers spend in the match rules, summed over workers;
clocked in the worker processes, added by the caller) over the window,
per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("matcher_verify_busy")
