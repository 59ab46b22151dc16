"""Readers shared by per-layer metrics.  A metric's file under
``benchmark/metrics/`` binds one of these to its stage clock or device
program; each returns None where it finds nothing to read."""


def stage_ms_per_1k(stage: str):
    """The program's ``stage`` clock (``obs/stages.py``) over the window,
    in milliseconds per 1,000 articles completed in it."""

    def read(ctx):
        ms = ctx.get("stage_ms", {}).get(stage)
        if not ms or not ctx["articles"]:
            return None
        return ms * 1000.0 / ctx["articles"]

    return read


def device_ms_per_1k(program: str):
    """Device time of every traced program whose name holds ``program``,
    in milliseconds per 1,000 articles completed in the window."""

    def read(ctx):
        seconds = ctx["trace"].seconds_of(program)
        if not seconds or not ctx["articles"]:
            return None
        return seconds * 1e6 / ctx["articles"]

    return read
