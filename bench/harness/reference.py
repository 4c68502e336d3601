"""Plain float32 reference of the configured model under LNS-8 numerics.

A straightforward ``jax.numpy`` decoder (RMSNorm, rotary attention with
grouped KV heads, gated SiLU MLP, tied embedding head) at matmul precision
``highest``, computing the quantization points the configuration states:

* weights: packed words decoded as ``±s·2^(-code/γ)``; projections read the
  words re-gridded onto the forward format, the embedding lookup reads them
  as stored, and the tied head quantizes the table per vocabulary column;
* activations (every projection input, and q, k, v): per-tensor
  power-of-two scale, round to nearest code;
* training only: the output cotangent of every projection and of the head
  (Q_E) and each weight gradient (Q_G) on the same grid, per tensor, and
  the multiplicative Madam step on the update words' exponent codes.

It imports nothing of the program and takes nothing the program made: it
remakes the weights from the seed with ``lnsgen`` and the tokens with
``data``. Layer and leaf names follow the program's parameter tree, so the
two sides' per-leaf readings can be compared by name.

This is also the dense architecture module, the one a configuration gets
when it names no ``reference`` (``bench/harness/spec.py``). An architecture
module is everything architecture-specific the harness reads, and exports:

* ``MODEL_KEYS``: Hugging Face ``config.json`` key -> ``ArchConfig`` field,
  applied over the program's registry entry;
* ``NOT_APPLIED``: keys it records but does not apply, each with its reason
  (any other key of the file's ``model`` is refused by name);
* ``Dims``, hashable, with ``Dims.from_model(model)``, which raises
  ``ValueError`` naming a key whose value the module does not compute;
* ``train_flops_per_token(dims, seq)`` and ``decode_row_flops(dims, c)``:
  model FLOPs of one training token at sequence length ``seq`` and of one
  decode row at context ``c``, counting active parameters only (the
  experts a token runs and the shared ones, never every expert);
* ``TrainSpec``, ``train_readings(words, batch_fn, dims, spec, steps)`` and
  ``serve_logits(words, tokens, n_valid, dims, store, fwd)``.

This module computes no experts, latent attention, sliding window, QKV
bias or untied head: a configuration with any of them names its own.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import lnsgen

WEIGHT_STREAM = 1          # the stream the benchmark's weight maker uses
TINY = float(np.finfo(np.float32).tiny)

PROJ = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/up", "mlp/gate",
        "mlp/down")
GAINS = ("ln1", "ln2")
EMBED = "embed/tok"
FINAL = "final_norm"


# Hugging Face config.json key -> the program's ArchConfig field
MODEL_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "hidden_act": "act_fn",
    "attention_bias": "qkv_bias",
}

NOT_APPLIED = {
    "max_position_embeddings": "rotary positions are computed, not "
                               "tabled; the traffic keeps sequences within "
                               "it",
}

# (key, the one value computed here, the value a file without the key
# means, what it computes): a file states its head, since families differ
COMPUTED = (
    ("tie_word_embeddings", True, None, "a tied head"),
    ("attention_bias", False, False, "projections without bias"),
    ("hidden_act", "silu", "silu", "the gated SiLU MLP"),
)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def from_model(cls, m: Dict) -> "Dims":
        for key, computed, absent, what in COMPUTED:
            if m.get(key, absent) != computed:
                raise ValueError(f"model key {key} = {m.get(key, absent)!r}"
                                 f": this module computes {what} only; name "
                                 f"an architecture module with 'reference'")
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv=m["num_key_value_heads"], hd=m["head_dim"],
                   ff=m["intermediate_size"], vocab=m["vocab_size"],
                   eps=m["rms_norm_eps"], theta=m["rope_theta"])

    def proj_shape(self, name: str):
        d, q, k, f = self.d, self.heads * self.hd, self.kv * self.hd, self.ff
        return {"attn/wq": (d, q), "attn/wk": (d, k), "attn/wv": (d, k),
                "attn/wo": (q, d), "mlp/up": (d, f), "mlp/gate": (d, f),
                "mlp/down": (f, d)}[name]


def matmul_params(dims: Dims) -> int:
    """N: parameters of every matmul the forward pass runs (the tied head
    included, the embedding lookup not)."""
    d, q, k = dims.d, dims.heads * dims.hd, dims.kv * dims.hd
    per_layer = d * q + 2 * d * k + q * d + 3 * d * dims.ff
    return dims.layers * per_layer + dims.vocab * d


def attn_width(dims: Dims) -> int:
    return dims.heads * dims.hd


def train_flops_per_token(dims: Dims, seq: int) -> float:
    """6 N + 12 L d_attn s: attention's score and value products at full
    width; recomputation is not counted."""
    return 6.0 * matmul_params(dims) + 12.0 * dims.layers * attn_width(
        dims) * seq


def decode_row_flops(dims: Dims, context: int) -> float:
    """2 N + 4 L d_attn c for a row at context ``c`` (the new token
    included)."""
    return 2.0 * matmul_params(dims) + 4.0 * dims.layers * attn_width(
        dims) * context


def leaf_name(name: str) -> str:
    """The program's path for a per-layer leaf."""
    return f"period/pos0/{name}"


# ---------------------------------------------------------------------------
# the number format


def pow2_scale(x, axis=None, valid=None):
    """Power-of-two scale at or above the absmax (``axis`` kept); rows
    where ``valid`` is False do not count."""
    a = jnp.abs(x.astype(jnp.float32))
    if valid is not None:
        a = jnp.where(valid, a, 0.0)
    if axis is None:
        amax = jnp.max(a)
    else:
        red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        amax = jnp.max(a, axis=red, keepdims=True)
    return jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, TINY))))


def quantize(x, fmt, scale):
    """Round ``x`` onto ``{±scale·2^(-e/γ)}``, ``e`` in ``[0, 2^(B-1)-1]``."""
    bits, gamma = fmt
    max_code = (1 << (bits - 1)) - 1
    mag = jnp.maximum(jnp.abs(x) / scale, TINY)
    code = jnp.clip(jnp.floor(-jnp.log2(mag) * gamma + 0.5), 0, max_code)
    return jnp.where(x < 0, -1.0, 1.0) * scale * jnp.exp2(-code / gamma)


def quantize_tensor(x, fmt, valid=None):
    return quantize(x, fmt, pow2_scale(x, valid=valid))


# ---------------------------------------------------------------------------
# weights remade from the seed


def _key(words, path):
    return lnsgen.path_key(lnsgen.root_key(words, WEIGHT_STREAM), path)


def proj_words(words, dims: Dims, name: str, layer: int, fmt):
    shape = dims.proj_shape(name)
    key = jax.random.fold_in(_key(words, leaf_name(name)), layer)
    return lnsgen.packed_slice(key, shape, 1.0 / math.sqrt(shape[0]), *fmt)


def gain(words, dims: Dims, name: str, layer: int):
    key = jax.random.fold_in(_key(words, leaf_name(name)), layer)
    return lnsgen.gain_values(key, (dims.d,))


def embed_words(words, dims: Dims, fmt):
    return lnsgen.packed_slice(_key(words, EMBED), (dims.vocab, dims.d),
                               0.02, *fmt)


def final_gain(words, dims: Dims):
    return lnsgen.gain_values(_key(words, FINAL), (dims.d,))


def all_layers(words, dims: Dims, fmt):
    """Every leaf, per-layer leaves stacked on a leading axis: the packed
    words and scales of each matrix and the float gains."""
    out = {}
    for name in PROJ:
        shape = dims.proj_shape(name)
        out[leaf_name(name)] = lnsgen.packed_stack(
            _key(words, leaf_name(name)), dims.layers, shape,
            1.0 / math.sqrt(shape[0]), *fmt)
    for name in GAINS:
        out[leaf_name(name)] = lnsgen.gain_stack(
            _key(words, leaf_name(name)), dims.layers, (dims.d,))
    out[EMBED] = embed_words(words, dims, fmt)
    out[FINAL] = final_gain(words, dims)
    return out


# ---------------------------------------------------------------------------
# the decoder


def rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def rope(x, pos, theta):
    """Rotate-half rotary embedding; ``x`` (..., S, H, D), ``pos`` (S,)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp2(-jnp.log2(theta) * jnp.arange(half, dtype=jnp.float32)
                     / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs      # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, dims: Dims, block: int = 512):
    """Causal attention, ``q`` (B, S, H, D), ``k``/``v`` (B, S, KV, D), in
    blocks of ``block`` queries, each recomputed in the backward pass, so
    one block's scores are the largest buffer."""
    B, S = q.shape[:2]
    rep = dims.heads // dims.kv
    blk = min(block, S)
    if S % blk:
        blk = S
    qg = q.reshape(B, S // blk, blk, dims.kv, rep, dims.hd).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        qb, i = args
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) / math.sqrt(dims.hd)
        qpos = i * blk + jnp.arange(blk)
        mask = qpos[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v)

    o = jax.lax.map(one, (qg, jnp.arange(S // blk)))
    return o.swapaxes(0, 1).reshape(B, S, dims.heads * dims.hd)


def make_qmm(fmt, train: bool):
    """``Q_A(x) @ w``; in training the output cotangent is quantized (Q_E)
    and the weight gradient is the straight-through ``Q_A(x)^T g``."""
    def fwd_only(x, w, valid):
        return quantize_tensor(x, fmt, valid) @ w

    if not train:
        return fwd_only

    @jax.custom_vjp
    def qmm(x, w, valid):
        return fwd_only(x, w, valid)

    def fwd(x, w, valid):
        xq = quantize_tensor(x, fmt, valid)
        return xq @ w, (xq, w, valid)

    def bwd(res, dy):
        xq, w, valid = res
        g = quantize_tensor(dy, fmt)
        dx = g @ w.T
        dw = xq.reshape(-1, xq.shape[-1]).T @ g.reshape(-1, g.shape[-1])
        return dx, dw, None

    qmm.defvjp(fwd, bwd)
    return qmm


def layer(x, p, pos, valid, dims: Dims, fmt, qmm):
    """One decoder layer on ``x`` (B, S, d); ``p`` holds the forward
    weight values and the two gains."""
    B, S, _ = x.shape
    h = rms_norm(x, p["ln1"], dims.eps)
    q = qmm(h, p["attn/wq"], valid).reshape(B, S, dims.heads, dims.hd)
    k = qmm(h, p["attn/wk"], valid).reshape(B, S, dims.kv, dims.hd)
    v = qmm(h, p["attn/wv"], valid).reshape(B, S, dims.kv, dims.hd)
    q, k = rope(q, pos, dims.theta), rope(k, pos, dims.theta)
    vq = None if valid is None else valid[..., None]
    q = jax.lax.stop_gradient(quantize_tensor(q, fmt, vq) - q) + q
    k = jax.lax.stop_gradient(quantize_tensor(k, fmt, vq) - k) + k
    v = jax.lax.stop_gradient(quantize_tensor(v, fmt, vq) - v) + v
    x = x + qmm(attention(q, k, v, dims), p["attn/wo"], valid)
    h = rms_norm(x, p["ln2"], dims.eps)
    u = jax.nn.silu(qmm(h, p["mlp/gate"], valid)) * qmm(h, p["mlp/up"],
                                                        valid)
    return x + qmm(u, p["mlp/down"], valid)


def head_weight(table, fmt):
    """The tied head: the embedding table, quantized per vocab column."""
    t = table.T
    return jax.lax.stop_gradient(quantize(t, fmt, pow2_scale(t, axis=-1))
                                 - t) + t


# ---------------------------------------------------------------------------
# training: three steps of LNS-Madam from the seed


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    fwd: tuple          # (bits, gamma) of weights, activations, Q_E, Q_G
    upd: tuple          # (bits, gamma) of the stored update words
    lr: float
    beta: float
    eps: float = 1e-30
    fp_clip: float = 10.0
    chunk: int = 1024   # head rows per block


def _xent_head(fmt, chunk):
    """Mean next-token cross entropy of ``Q_A(x) @ wq`` over rows, in row
    blocks, with Q_E on the logits' cotangent (one scale over all rows)."""

    def blocks(a):
        n = a.shape[0]
        return a.reshape(n // chunk, chunk, *a.shape[1:])

    def logits_of(xq_blk, wq):
        return xq_blk @ wq

    @jax.custom_vjp
    def f(x, wq, labels):
        return fwd(x, wq, labels)[0]

    def fwd(x, wq, labels):
        xq = quantize_tensor(x, fmt)

        def one(args):
            xb, lb = args
            lg = logits_of(xb, wq)
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
            return jnp.sum(lse - gold)

        total = jnp.sum(jax.lax.map(one, (blocks(xq), blocks(labels))))
        return total / x.shape[0], (xq, wq, labels)

    def bwd(res, gl):
        xq, wq, labels = res
        n = xq.shape[0]

        def cot(xb, lb):
            lg = logits_of(xb, wq)
            p = jax.nn.softmax(lg, axis=-1)
            onehot = jax.nn.one_hot(lb, lg.shape[-1], dtype=p.dtype)
            return (p - onehot) * (gl / n)

        amax = jnp.max(jax.lax.map(lambda a: jnp.max(jnp.abs(cot(*a))),
                                   (blocks(xq), blocks(labels))))
        scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, TINY))))

        def acc(dw, args):
            xb, lb = args
            g = quantize(cot(xb, lb), fmt, scale)
            return dw + xb.T @ g, g @ wq.T

        dw, dx = jax.lax.scan(acc, jnp.zeros_like(wq),
                              (blocks(xq), blocks(labels)))
        return dx.reshape(n, -1), dw, None

    f.defvjp(fwd, bwd)
    return f


def train_loss(diff, values, batch, dims: Dims, spec: TrainSpec):
    """Loss at ``values + diff`` (``diff`` holds zero carriers for the
    matrices and the gains themselves)."""
    fmt = spec.fwd
    qmm = make_qmm(fmt, train=True)
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    table = values[EMBED] + diff[EMBED]
    x = table[tokens]
    pos = jnp.arange(S)

    @jax.checkpoint
    def body(x, lp):
        return layer(x, lp, pos, None, dims, fmt, qmm), None

    stacked = {n: values[leaf_name(n)] + diff[leaf_name(n)] for n in PROJ}
    stacked.update({n: diff[leaf_name(n)] for n in GAINS})
    x, _ = jax.lax.scan(body, x, stacked)
    x = rms_norm(x, diff[FINAL], dims.eps)
    head = _xent_head(fmt, min(spec.chunk, B * S))
    return head(x.reshape(B * S, -1), head_weight(table, fmt),
                labels.reshape(-1))


def _madam_words(word, scale, g, v, count, spec: TrainSpec):
    bits, gamma = spec.upd
    max_code = (1 << (bits - 1)) - 1
    w = word.astype(jnp.int32)
    sign_bit = (w >> (bits - 1)) & 1
    code = (w & max_code).astype(jnp.float32)
    v = (1.0 - spec.beta) * g * g + spec.beta * v
    bc = 1.0 - spec.beta ** count.astype(jnp.float32)
    gstar = g * jax.lax.rsqrt(v / bc + spec.eps)
    sign = 1.0 - 2.0 * sign_bit.astype(jnp.float32)
    target = code + spec.lr * gamma * gstar * sign
    new = jnp.clip(jnp.floor(target + 0.5), 0, max_code).astype(jnp.int32)
    return ((sign_bit << (bits - 1)) | new).astype(word.dtype), v


def _madam_fp(p, g, v, count, spec: TrainSpec):
    v = (1.0 - spec.beta) * g * g + spec.beta * v
    bc = 1.0 - spec.beta ** count.astype(jnp.float32)
    gstar = g * jax.lax.rsqrt(v / bc + spec.eps)
    w = p * jnp.exp(-spec.lr * jnp.sign(p) * gstar)
    w = jnp.where(jnp.abs(p) < 1e-8, p - spec.lr * gstar * 1e-8, w)
    return jnp.clip(w, -spec.fp_clip, spec.fp_clip), v


def forward_values(state, spec: TrainSpec):
    """Decoded weight values the forward pass reads."""
    vals = {}
    for name in PROJ:
        w, s = state[leaf_name(name)]
        vals[leaf_name(name)] = lnsgen.decode_words(
            lnsgen.regrid_words(w, spec.upd, spec.fwd), s, *spec.fwd)
    w, s = state[EMBED]
    vals[EMBED] = lnsgen.decode_words(w, s, *spec.upd)
    return vals


def stored_values(state, spec: TrainSpec):
    """Every leaf as a float array: matrices decoded from the stored
    update words, gains as they are."""
    out = {}
    for k, v in state.items():
        out[k] = lnsgen.decode_words(v[0], v[1], *spec.upd) \
            if isinstance(v, tuple) else v
    return out


def train_step(state, v2, count, batch, dims: Dims, spec: TrainSpec):
    """One step: returns (state, v2, loss, quantized grads)."""
    vals = forward_values(state, spec)
    diff = {k: (jnp.zeros_like(vals[k]) if isinstance(v, tuple) else v)
            for k, v in state.items()}
    loss, grads = jax.value_and_grad(train_loss)(diff, vals, batch, dims,
                                                 spec)
    grads = {k: quantize_tensor(g, spec.fwd) for k, g in grads.items()}
    count = count + 1
    new_state, new_v2 = {}, {}
    for k, leaf in state.items():
        if isinstance(leaf, tuple):
            w, v = _madam_words(leaf[0], leaf[1], grads[k], v2[k], count,
                                spec)
            new_state[k] = (w, leaf[1])
        else:
            w, v = _madam_fp(leaf, grads[k], v2[k], count, spec)
            new_state[k] = w
        new_v2[k] = v
    return new_state, new_v2, count, loss, grads


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_readings(words, batch_fn, dims: Dims, spec: TrainSpec,
                   steps: int = 3):
    """Run ``steps`` reference steps from the seed. Returns host floats:
    ``loss`` per step, ``grad_norm`` per leaf at step 1 and ``change_norm``
    per leaf after the last step."""
    with jax.default_matmul_precision("highest"):
        state = jax.jit(lambda w: all_layers(w, dims, spec.upd))(words)
        v2 = {k: jnp.zeros_like(v[0], jnp.float32) if isinstance(v, tuple)
              else jnp.zeros_like(v) for k, v in state.items()}
        before = jax.jit(lambda s: stored_values(s, spec))(state)
        step = jax.jit(partial(train_step, dims=dims, spec=spec))
        count = jnp.zeros((), jnp.int32)
        losses, grad_norm = [], None
        for i in range(steps):
            state, v2, count, loss, grads = step(state, v2, count,
                                                 batch_fn(i))
            losses.append(float(loss))
            if i == 0:
                grad_norm = {k: float(v) for k, v in
                             jax.jit(leaf_norms)(grads).items()}
            del grads
        after = jax.jit(lambda s: stored_values(s, spec))(state)
        change = jax.jit(lambda a, b: leaf_norms(
            {k: a[k] - b[k] for k in a}))(after, before)
    return {"loss": losses, "grad_norm": grad_norm,
            "change_norm": {k: float(v) for k, v in change.items()}}


# ---------------------------------------------------------------------------
# serving: logits of one sequence, layer by layer


def serve_logits(words, tokens, n_valid, dims: Dims, store: tuple,
                 fwd: tuple):
    """Logits ``(S, V)`` of a right-padded sequence whose first
    ``n_valid`` rows are real, from weights stored as ``store`` words
    and computed on the ``fwd`` grid. Layers run one at a time, each
    remade from the seed, so the peak holds one layer's values."""
    S = tokens.shape[0]
    valid = (jnp.arange(S) < n_valid)[None, :, None]
    pos = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        table = _embed_table(words, dims, store)
        x = _lookup(table, tokens)
        for i in range(dims.layers):
            x = _serve_layer(words, x, pos, valid, jnp.int32(i), dims,
                             store, fwd)
        return _serve_head(words, x, table, valid, dims, fwd)


@partial(jax.jit, static_argnames=("dims", "store"))
def _embed_table(words, dims, store):
    w, s = embed_words(words, dims, store)
    return lnsgen.decode_words(w, s, *store)


@jax.jit
def _lookup(table, tokens):
    return table[tokens][None]


@partial(jax.jit, static_argnames=("dims", "store", "fwd"))
def _serve_layer(words, x, pos, valid, layer_ix, dims, store, fwd):
    p = {}
    for name in PROJ:
        w, s = proj_words(words, dims, name, layer_ix, store)
        p[name] = lnsgen.decode_words(lnsgen.regrid_words(w, store, fwd), s,
                                      *fwd)
    for name in GAINS:
        p[name] = gain(words, dims, name, layer_ix)
    return layer(x, p, pos, valid, dims, fwd, make_qmm(fwd, train=False))


@partial(jax.jit, static_argnames=("dims", "fwd"))
def _serve_head(words, x, table, valid, dims, fwd):
    x = rms_norm(x, final_gain(words, dims), dims.eps)
    xq = quantize_tensor(x[0], fwd, valid[0])
    return xq @ head_weight(table, fwd)
