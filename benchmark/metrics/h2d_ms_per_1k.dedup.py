"""Dispatch executor: the program's ``h2d`` stage clock (host-to-device
puts of packed tiles, ``pipeline/dispatch.py``) over the window, per
1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("h2d")
