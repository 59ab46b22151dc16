"""Screen plane: the program's ``matcher_screen_wait`` stage clock (the
caller blocked on each screen tile's mask and owner readback; span
``astpu.matcher.screen_wait``) over the window, per 1,000 articles."""

from benchmark.readers import stage_ms_per_1k

read = stage_ms_per_1k("matcher_screen_wait")
