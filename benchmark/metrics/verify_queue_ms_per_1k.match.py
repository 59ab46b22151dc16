"""Verify pool: the program's ``matcher_verify_queue`` stage clock (seconds
each verify slice waits from submit to a worker's start, summed over
slices; clocked in the worker processes, added by the caller) over the
window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("matcher_verify_queue")
