"""TPU-first primitive ops for the benchmark data plane.

These are the hot ops of the flagship inference workload scheduled by the
middleware (the reference repo has no tensor ops -- SURVEY.md §2.6; this layer
exists so the TTFT benchmark in `benchmarks/` and `bench.py` exercises a real
JAX/XLA model under vTPU isolation, mirroring the reference's vLLM harness,
reference benchmarks/ai-benchmark/benchmark.py:1-50).
"""

# The scope vocabulary: every part of a compiled step (decode, admission,
# chunk) runs under exactly one of these ``jax.named_scope`` names, so a
# profiler trace attributes device time to it (PERF.md section 3 says which
# metric reads which). A new step or kernel takes a name from here or adds
# one here and there.
SCOPES = (
    "embed", "qkv", "kv_write", "pool_relayout", "paged_attn", "gather_attn",
    "attn", "o_proj", "mlp", "route", "experts", "lm_head", "sample",
    "indexer", "select", "latent_attn", "ssm_conv", "ssm_scan", "ssm_gate",
    "window_attn", "ring_write", "block_attn", "chunk_attn",
)

from vtpu.ops.init import scaled_normal  # noqa: E402
from vtpu.ops.norms import rms_norm
from vtpu.ops.rope import apply_rope, rope_angles, yarn_rope_angles
from vtpu.ops.attention import (
    causal_attention,
    causal_attention_int8kv,
    flash_attention,
    gather_kv_pages,
    paged_causal_attention,
    paged_causal_attention_int8kv,
)
from vtpu.ops.decode_attn import (
    PAGED_ATTN_MIN_WINDOW,
    PAGED_ATTN_MIN_WINDOW_INT8,
    count_pool_gathers,
    decode_attention,
    paged_attn_route,
    paged_decode_attention,
    paged_decode_attention_int8kv,
)

__all__ = [
    "SCOPES",
    "scaled_normal",
    "rms_norm",
    "apply_rope",
    "rope_angles",
    "yarn_rope_angles",
    "causal_attention",
    "causal_attention_int8kv",
    "flash_attention",
    "gather_kv_pages",
    "paged_causal_attention",
    "paged_causal_attention_int8kv",
    "PAGED_ATTN_MIN_WINDOW",
    "PAGED_ATTN_MIN_WINDOW_INT8",
    "count_pool_gathers",
    "decode_attention",
    "paged_attn_route",
    "paged_decode_attention",
    "paged_decode_attention_int8kv",
]
