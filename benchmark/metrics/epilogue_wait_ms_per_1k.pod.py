"""Cross-chip epilogue: the program's ``sharded_epilogue`` clock (the
epilogue's dispatch and the readback of the representatives, on the
caller's thread) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("sharded_epilogue")
