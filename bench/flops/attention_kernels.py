"""Operations and bytes of the attention kernels, counted from their shapes."""

from __future__ import annotations

BF16 = 2


def flash_causal(batch: int, seq: int, heads: int, kv_heads: int, hd: int) -> tuple[float, float]:
    """Forward causal flash attention: QK^T and PV over the lower triangle;
    reads q, k, v and writes o once."""
    flops = 2.0 * 2.0 * batch * heads * hd * seq * seq / 2.0
    nbytes = BF16 * batch * seq * hd * (2 * heads + 2 * kv_heads)
    return flops, nbytes


def paged_decode(live_sum: int, slots: int, heads: int, kv_heads: int, hd: int) -> tuple[float, float]:
    """One decode token per slot over ``live_sum`` live cache positions in
    all: QK^T and PV; reads the live K and V pages, q, and writes o."""
    flops = 4.0 * heads * hd * live_sum
    nbytes = BF16 * (2 * live_sum * kv_heads * hd + 2 * slots * heads * hd)
    return flops, nbytes
