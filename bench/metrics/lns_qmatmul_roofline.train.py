"""lns_qmatmul's share of its roofline in the train step, in percent:
each call's least time (2 M K N FLOPs with M the rows before padding, and
its packed operands and float32 output in bytes) summed, over the summed
device time of the same calls (kernels layer; moves train_tokens_per_s)."""
from bench.harness.readers import kernel_roofline, qmatmul_call_work


def read(rec):
    return kernel_roofline(rec, "lns_qmatmul_pallas", "train_step",
                           qmatmul_call_work)
