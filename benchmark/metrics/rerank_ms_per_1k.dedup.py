"""Precision tier: host time inside the engine's rerank hook
(``pipeline/rerank.py``: candidate work-list, sketches, settle tiles,
margin re-settle, eviction), timed by the benchmark's wrapper after the
signatures are ready, over the window, per 1,000 articles."""


def read(ctx):
    s = ctx.get("hook_s")
    if not s or not ctx["articles"]:
        return None
    return s * 1e6 / ctx["articles"]
