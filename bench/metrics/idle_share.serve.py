"""Share of the traced window in which no operation ran on the chip, in
percent, for a serving cell (device layer; moves tpot_p95_ms)."""
from bench.harness.readers import idle_share


def read(rec):
    return idle_share(rec) if rec.get("serve") is not None else None
