"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

A reader is ``bench/metrics/<metric name>.py`` with ``read(rec)``: it turns
the run record (the cell's counters and timestamps, the trace reduction and
the compiled programs' kernel calls) into one number, or ``None`` when the
run has nothing for it to read.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Callable, Optional

from bench.harness import flops

METRICS_DIR = Path(__file__).resolve().parents[1] / "metrics"


def load(name: str) -> Callable[[dict], Optional[float]]:
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kernel_roofline(rec: dict, kernel: str, module_key: str,
                    work) -> Optional[float]:
    """Percent of the least time over the measured device time, summed
    over every traced call of ``kernel`` in a program whose name holds
    ``module_key``. ``work(call)`` gives a call's (FLOPs, bytes) from its
    operand shapes."""
    red = rec.get("trace")
    programs = rec.get("programs") or {}
    if red is None:
        return None
    pk = rec["peaks"]
    t_least = t_meas = 0.0
    for ev in red.ops:
        if module_key not in ev.module or ev.module not in programs:
            continue
        call = programs[ev.module].get(ev.name)
        if call is None or call["kernel"] != kernel:
            continue
        f, b = work(call)
        t_least += flops.least_time(f, b, pk)
        t_meas += ev.dur
    if t_meas <= 0:
        return None
    return 100.0 * t_least / t_meas


def qmatmul_call_work(call: dict):
    """The packed GEMM's real ``(M, K) @ (K, N)``, before its padding to
    128-multiples."""
    (m, k), (k2, n) = call["real"][:2]
    return flops.qmatmul_work(m, min(k, k2), n)


def madam_call_work(call: dict):
    """The packed update kernel: words, gradient and second moment of the
    leaf's real size."""
    words = [o for o in call["operands"] if len(o[2]) >= 2
             and tuple(o[2]) != (1, 1)][0]
    elements = 1
    for d in call["real"][0]:
        elements *= d
    word_bytes = {"u8": 1, "u16": 2, "u32": 4}[words[0]]
    return flops.madam_work(elements, word_bytes)


def idle_share(rec: dict) -> Optional[float]:
    red = rec.get("trace")
    if red is None or red.window_s <= 0 or not red.devices:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
