"""Kernels: device time of the matcher's packed screen-step programs
(``ops/match.py`` ``screen_step``) in the traced window, per 1,000
articles."""

from benchmark.readers import device_ms_per_1k

read = device_ms_per_1k("screen_step")
