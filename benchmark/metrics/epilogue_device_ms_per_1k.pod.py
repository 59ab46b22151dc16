"""Cross-chip epilogue: device time of the sharded resolve programs
(``parallel/sharded_packed.py`` ``sharded_resolve``: the ``pmin`` across
the chips, then the replicated resolve) in the traced window, per chip,
per 1,000 articles."""

from benchmark.readers import device_ms_per_1k

read = device_ms_per_1k("sharded_resolve")
