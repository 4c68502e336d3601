"""Mean live rows per decode step in the window (before a traced run's
trace starts): tokens the decode steps emitted over decode steps (engine
layer; moves serve_tokens_per_s)."""


def read(rec):
    serve = rec.get("serve")
    if serve is None or not serve["counters"]["host_decode_steps"]:
        return None
    c = serve["counters"]
    return c["decode_tokens"] / c["host_decode_steps"]
