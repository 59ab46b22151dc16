"""Kernels: the fused tile step's share of its roofline.

Device time: every run of the ``fused_tile_step`` program in the traced
window.  Work: the MinHash of the real k-byte windows of every article
the window completed (``benchmark/roofline.py``), so padding, block
overlap and idle lanes count as lost time.  Bound: the larger of the
operations over the derived VPU rate and the bytes over HBM bandwidth.
"""

from benchmark import roofline


def read(ctx):
    seconds = ctx["trace"].seconds_of("fused_tile_step")
    if not seconds or not ctx.get("shingles"):
        return None
    ops = roofline.minhash_ops(ctx["shingles"], ctx["num_perm"], ctx["shingle_k"])
    nbytes = roofline.minhash_bytes(ctx["article_bytes"], ctx["articles"], ctx["num_perm"])
    share, bound = roofline.roofline_share(ops, nbytes, seconds, ctx["device_kind"], "vpu_ops")
    ctx["say"]("minhash_tile roofline", device_s=seconds, ops=ops, bytes=nbytes, bound=bound)
    return share
