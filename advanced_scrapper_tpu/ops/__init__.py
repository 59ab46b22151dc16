from advanced_scrapper_tpu.ops.shingle import fmix32, shingle_hash
from advanced_scrapper_tpu.ops.minhash import (
    combine_block_signatures,
    minhash_signatures,
)
from advanced_scrapper_tpu.ops.lsh import (
    band_keys,
    bucket_histogram,
    candidate_keys,
    duplicate_rep_bands,
    duplicate_reps,
    resolve_rep_bands,
    resolve_reps,
)
from advanced_scrapper_tpu.ops.exact import row_hash128

__all__ = [
    "fmix32",
    "shingle_hash",
    "minhash_signatures",
    "combine_block_signatures",
    "band_keys",
    "candidate_keys",
    "duplicate_reps",
    "duplicate_rep_bands",
    "resolve_reps",
    "resolve_rep_bands",
    "bucket_histogram",
    "row_hash128",
]
