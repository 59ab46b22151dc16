"""Verify pool: the program's ``matcher_verify`` stage clock (the caller
waiting on the ``match_article`` workers, ``pipeline/matcher.py``) over
the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("matcher_verify")
