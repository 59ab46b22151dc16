"""One-shot resolve: the program's ``resolve_wait`` stage clock (from the
hook's return to the representatives on the host: edge mask, resolve
dispatch, readback; span ``astpu.dedup.resolve``) over the window, per
1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("resolve_wait")
