"""A thin architecture module over the dense one that counts its calls.

A test configuration names it (``"reference": "counting_arch.py"``, with
the module entered as ``bench.harness.counting_arch``) to see which module
the harness calls. It computes what the dense module computes, and maps
some expert and latent-attention keys besides, which only
``spec.arch_config`` reads here.
"""
import dataclasses
from collections import Counter

from bench.harness import reference as dense

CALLS = Counter()

# keys only this module maps (the dense module refuses them)
EXTRA_KEYS = {
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "num_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_rope_head_dim": "qk_rope_dim",
}
MODEL_KEYS = {**dense.MODEL_KEYS, **EXTRA_KEYS}
NOT_APPLIED = dict(dense.NOT_APPLIED)
TrainSpec = dense.TrainSpec

# the dense module's own functions, taken before a test can replace them
_from_model = dense.Dims.__dict__["from_model"].__func__
_train_readings = dense.train_readings
_serve_logits = dense.serve_logits
_train_flops = dense.train_flops_per_token
_decode_row_flops = dense.decode_row_flops


@dataclasses.dataclass(frozen=True)
class Dims(dense.Dims):
    @classmethod
    def from_model(cls, m):
        CALLS["from_model"] += 1
        return _from_model(cls, {k: v for k, v in m.items()
                                 if k not in EXTRA_KEYS})


def train_readings(*args, **kw):
    CALLS["train_readings"] += 1
    return _train_readings(*args, **kw)


def serve_logits(*args, **kw):
    CALLS["serve_logits"] += 1
    return _serve_logits(*args, **kw)


def train_flops_per_token(dims, seq):
    CALLS["train_flops_per_token"] += 1
    return _train_flops(dims, seq)


def decode_row_flops(dims, context):
    CALLS["decode_row_flops"] += 1
    return _decode_row_flops(dims, context)
