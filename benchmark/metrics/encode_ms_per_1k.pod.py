"""Host encode and pack: the program's ``encode`` stage clock (summed
over the packing threads) over the window, per 1,000 articles, on the
sharded plane."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("encode")
