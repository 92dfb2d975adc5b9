"""paddle_tpu.serving.spec — speculative decoding for the serving engine.

Decode is memory-bandwidth-bound (the paged kernel runs near the HBM
roofline — BENCH_OPS.md), so per-sequence tokens/step is the
remaining throughput lever. The reference serves this need through its
fused multi-token attention paths (`block_multi_head_attention` /
`masked_multihead_attention`, SURVEY A.2); the TPU-native analog built
here is speculative decoding: a cheap PROPOSER drafts K candidate
tokens per sequence, ONE bucketed `("verify", B, K, P)` launch scores
all of them against the paged cache, and the engine keeps the longest
verified prefix plus one correction/bonus token — amortizing a single
paged-attention pass over up to K+1 emitted tokens. Rejected drafts
roll back via `BlockAllocator.truncate_sequence` with refcount/CoW/
radix invariants intact. See SERVING.md "Speculative decoding".
"""
from .draft_model import DraftModelProposer
from .proposer import NgramProposer, Proposer

__all__ = ["Proposer", "NgramProposer", "DraftModelProposer"]
