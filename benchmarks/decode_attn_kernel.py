"""Pallas decode-attention study surface — now a thin shim over the PRODUCT
kernel module ``vtpu/ops/decode_attn.py``.

History: standalone, the fused
dense-cache kernel beats XLA at the T=1 long-window cells
(DECODE_ATTN_r05.json, two-chain-difference timing — bf16 1.1-1.6x from
window 1024, int8 1.9x at 2048, ~760 GB/s; int8@1024 and T=4 chunks lost —
the shipped auto router keys on exactly those cells). In the TRUNK it lost
everywhere: a pallas operand must be materialized while the
serving cache is being scatter-updated, so XLA copied the layer view — the
copy cost more than the kernel saved, r6 removed the route and parked the
kernel here. The park verdict named what re-promotion needed: a shard_map
wrapper for ('tp',) meshes, and input/output aliasing so the cache feeds
the kernel without materialization.

BOTH shipped with the paged pool route (ISSUE 10): ``paged_decode_attention``
in vtpu/ops/decode_attn.py takes the whole donated block pool as its operand
(nothing to materialize — the scatter-updated buffer aliases straight in),
walks the page table via scalar prefetch, wraps in shard_map under a ('tp',)
mesh, and speaks int8 natively. The serving trunk routes to it per measured
shape (paged_attn_route); the dense study kernel lives on in the product
module unchanged so its standalone numbers stay re-checkable —
hack/decode_attn_bench.py drives ``decode_attention`` through this import
exactly as before.

Equals causal_attention / causal_attention_int8kv on the same operands
(tests/test_ops.py asserts both, driving this module directly).
"""

from __future__ import annotations

from vtpu.ops.decode_attn import decode_attention

__all__ = ["decode_attention"]
