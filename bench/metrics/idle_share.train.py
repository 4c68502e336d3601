"""Share of the traced window in which no operation ran on the chip, in
percent, for a training cell (device layer; moves train_tokens_per_s)."""
from bench.harness.readers import idle_share


def read(rec):
    return idle_share(rec) if rec.get("train") is not None else None
