"""Kernels: the sharded fused tile step's share of one chip's roofline.

Device time: every run of the ``sharded_tile_step`` program in the traced
window, per chip (the trace's program times are averaged over the device
planes).  Work: the MinHash of the real k-byte windows of every article
the window completed (``benchmark/roofline.py``), divided by the chips
of the mesh, since each chip folds its share of the tiles.  Bound: the
larger of the operations over one chip's derived VPU rate and the bytes
over its HBM bandwidth.
"""

from benchmark import roofline


def read(ctx):
    seconds = ctx["trace"].seconds_of("sharded_tile_step")
    if not seconds or not ctx.get("shingles") or not ctx.get("chips"):
        return None
    chips = ctx["chips"]
    ops = roofline.minhash_ops(ctx["shingles"], ctx["num_perm"], ctx["shingle_k"]) / chips
    nbytes = roofline.minhash_bytes(ctx["article_bytes"], ctx["articles"], ctx["num_perm"]) / chips
    share, bound = roofline.roofline_share(ops, nbytes, seconds, ctx["device_kind"], "vpu_ops")
    ctx["say"]("shard_tile roofline", device_s=seconds, chips=chips, ops_per_chip=ops,
               bytes_per_chip=nbytes, bound=bound)
    return share
