"""Model FLOPs of a training step over its device time and the chip's
bf16 peak, in percent (model step layer; moves train_tokens_per_s): the
step's tokens times 6 N + 12 L d_attn s (N every matmul parameter the
forward uses, the tied head included), over the mean device time of the
train-step runs the trace holds whole. The device is idle for ~0.1% of a
step, so this is the window's rate without the profiler's own stalls."""
import statistics

from bench.harness import flops


def read(rec):
    train, red = rec.get("train"), rec.get("trace")
    if train is None or red is None:
        return None
    runs = [d for k, v in red.module_whole.items() if "train_step" in k
            for d in v]
    if not runs:
        return None
    per_tok = flops.train_flops_per_token(rec["dims"], train["seq"])
    rate = train["tokens_per_step"] / statistics.mean(runs)
    return 100.0 * per_tok * rate / rec["peaks"]["bf16_flops_per_s"]
