"""Model FLOPs of the traced decode steps, at their live rows and context
lengths, over the device time of the decode program in the trace and the
chip's bf16 peak, in percent (model step layer; moves tpot_p95_ms)."""


def read(rec):
    serve, red = rec.get("serve"), rec.get("trace")
    if serve is None or red is None:
        return None
    t = sum(v for k, v in red.module_time.items() if "decode_sample" in k)
    f = serve.get("traced_decode_flops", 0.0)
    if t <= 0 or f <= 0:
        return None
    return 100.0 * f / t / rec["peaks"]["bf16_flops_per_s"]
