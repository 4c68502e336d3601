"""Operations and bytes computed from shapes, and the chip's peaks.

Model FLOPs are the architecture module's (``reference.py`` states the
interface): active parameters only, recomputation not counted. Kernel
counts are per call, from the call's own operand shapes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from bench.harness import spec

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def train_flops_per_token(dims, seq: int) -> float:
    """Model FLOPs of one training token at sequence length ``seq``, as
    the architecture module that made ``dims`` counts them."""
    return spec.module_of(dims).train_flops_per_token(dims, seq)


def decode_flops(dims, contexts) -> float:
    """Model FLOPs of one decode step of rows at the given context lengths
    (the new token included), each row as the dims' module counts it."""
    row = spec.module_of(dims).decode_row_flops
    return sum(row(dims, c) for c in contexts)


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
