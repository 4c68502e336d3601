"""The packed Madam update kernel's share of its roofline in the train
step, in percent: each call's words, gradient and second moment read and
words and second moment written, at the leaf's size before padding, over
the HBM bandwidth, summed, over the summed device time of the same calls
(kernels layer; moves train_tokens_per_s)."""
from bench.harness.readers import kernel_roofline, madam_call_work


def read(rec):
    return kernel_roofline(rec, "madam_update_packed_pallas", "train_step",
                           madam_call_work)
