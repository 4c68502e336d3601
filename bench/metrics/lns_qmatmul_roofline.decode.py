"""lns_qmatmul's share of its roofline in the engine's decode step, in
percent, counted as in lns_qmatmul_roofline.train: M is the live slot
count the call was given before its padding to 128 rows (kernels layer;
moves tpot_p95_ms)."""
from bench.harness.readers import kernel_roofline, qmatmul_call_work


def read(rec):
    return kernel_roofline(rec, "lns_qmatmul_pallas", "decode_sample",
                           qmatmul_call_work)
