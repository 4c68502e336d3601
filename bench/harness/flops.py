"""Operations and bytes computed from shapes, and the chip's peaks.

Model FLOPs count every matmul parameter the forward pass uses (the tied
head included, the embedding lookup not) and attention's score and value
products at full width; recomputation is not counted. Kernel counts are
per call, from the call's own operand shapes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def matmul_params(dims) -> int:
    """N: parameters of every matmul the forward pass runs."""
    d, q, k = dims.d, dims.heads * dims.hd, dims.kv * dims.hd
    per_layer = d * q + 2 * d * k + q * d + 3 * d * dims.ff
    return dims.layers * per_layer + dims.vocab * d


def attn_width(dims) -> int:
    return dims.heads * dims.hd


def train_flops_per_token(dims, seq: int) -> float:
    """6 N + 12 L d_attn s."""
    return 6.0 * matmul_params(dims) + 12.0 * dims.layers * attn_width(
        dims) * seq


def decode_flops(dims, contexts) -> float:
    """One decode step of rows at the given context lengths (the new
    token included): 2 N + 4 L d_attn c per row."""
    n2 = 2.0 * matmul_params(dims)
    la = 4.0 * dims.layers * attn_width(dims)
    return sum(n2 + la * c for c in contexts)


def qmatmul_work(m: int, k: int, n: int, a_bytes: int = 1, b_bytes: int = 1,
                 out_bytes: int = 4):
    """(FLOPs, bytes) of a packed ``(m, k) @ (k, n)`` call: the packed
    operands read once and the float32 output written once."""
    return 2.0 * m * k * n, float(m * k * a_bytes + k * n * b_bytes
                                  + m * n * out_bytes)


def madam_work(elements: int, word_bytes: int):
    """(FLOPs, bytes) of a packed Madam update: word, gradient and second
    moment read, word and second moment written. Its arithmetic is
    elementwise and bounded by bytes, so no matrix FLOPs are counted."""
    return 0.0, float(elements * (2 * word_bytes + 4 + 4 + 4))


def least_time(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
