"""Compute hot-spots Bullet optimizes: attention (prefill + decode) and
the fused prefill+decode co-execution schedule, plus the recurrent scans
the SSM/hybrid assigned architectures need. Validated against ref.py
oracles in interpret mode (tests/test_kernels.py)."""

from repro.kernels.ops import (
    flash_attention_op,
    decode_attention_op,
    paged_decode_attention_op,
    bullet_attention_op,
    bullet_attention_paged_op,
    rglru_scan_op,
    ssd_scan_op,
)
