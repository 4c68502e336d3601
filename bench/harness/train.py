"""Training cells: the program's LNS-Madam train step, driven from the seed.

Set-up builds one object, the jitted step with its donated state, and
drives it through its first three steps with the window's own call and
feed; those steps are what the plain reference is compared against. The
window then runs whole steps of the same object until ``--seconds`` have
passed, blocking on each step's loss.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.harness import data, lnsgen, spec, weights

CHECK_STEPS = 3


def build_step(cfg, qcfg, mcfg):
    """The program's train step (the timed path)."""
    from repro.training.steps import build_train_step
    return build_train_step(cfg, qcfg, mcfg)


class TrainJob:
    """The cell's program objects: config, step, state maker and feed.
    With a ``mesh`` the state and each batch are made straight into the
    program's shardings under the training rules; without one the step
    runs on a one-device mesh and the state is made on that device."""

    def __init__(self, cell: spec.Cell, batch: Optional[int] = None,
                 update_bits: Optional[int] = None, mesh=None):
        from repro.core.quantizer import QuantConfig
        from repro.distributed.params_sharding import batch_shardings
        from repro.distributed.sharding import shard_ctx
        from repro.launch.mesh import make_host_mesh
        from repro.configs import get_rules
        from repro.optim.madam import MadamConfig

        opts = cell.config["train"]
        q = opts["quant"]
        self.cfg = spec.arch_config(cell.config)
        ubits = update_bits or q["update_bits"]
        self.qcfg = QuantConfig.lns_madam(bits=q["bits"], gamma=q["gamma"],
                                          update_bits=ubits)
        self.mcfg = MadamConfig(lr=opts["madam"]["lr"],
                                beta=opts["madam"]["beta"],
                                update_format=self.qcfg.update)
        self.batch = batch or cell.traffic["batch"]
        self.seq = cell.traffic["seq"]
        self.noise = cell.traffic.get("noise_levels", 16)
        self.dims = cell.dims
        self.mesh = make_host_mesh(1, 1) if mesh is None else mesh
        self.rules = get_rules(cell.config["arch"])
        step = build_step(self.cfg, self.qcfg, self.mcfg)

        def train_step(state, batch):
            with shard_ctx(self.mesh, self.rules):
                return step(state, batch)

        if mesh is None:
            self.step_fn = jax.jit(train_step, donate_argnums=(0,))
            self.state_fn = jax.jit(self.make_state)
            self.batch_fn = jax.jit(self.make_batch)
            return
        words = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state_sh = weights.train_state_shardings(
            jax.eval_shape(self.make_state, words), mesh, self.rules)
        batch = jax.eval_shape(self.make_batch, words,
                               jax.ShapeDtypeStruct((), jnp.int32))
        # the step hands the state back in the shardings it was made in
        # (not merely equivalent ones), so one compiled step serves all
        self.step_fn = jax.jit(train_step, donate_argnums=(0,),
                               out_shardings=(state_sh, None))
        self.state_fn = jax.jit(self.make_state, out_shardings=state_sh)
        self.batch_fn = jax.jit(self.make_batch, out_shardings=(
            batch_shardings(batch, mesh, self.rules)))

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def make_state(self, words):
        return weights.make_train_state(words, self.cfg, self.mcfg)

    def make_batch(self, words, index):
        return data.make_batch(words, index, batch=self.batch, seq=self.seq,
                               vocab=self.cfg.vocab_size, noise=self.noise)


def ref_spec(cell: spec.Cell):
    """The reference's numerics (its module's ``TrainSpec``), read from
    the configuration file."""
    opts = cell.config["train"]
    q, m = opts["quant"], opts["madam"]
    ubits = q["update_bits"]
    # the update grid keeps the forward format's range: gamma scales with
    # the added bits (paper section 6.1.1)
    ugamma = q["gamma"] << max(ubits - q["bits"], 0)
    return cell.arch.TrainSpec(fwd=(q["bits"], q["gamma"]),
                               upd=(ubits, ugamma), lr=m["lr"],
                               beta=m["beta"])


# ---------------------------------------------------------------------------
# readings of the program's state (the comparison side)


def _by_name(tree) -> Dict[str, object]:
    from repro.core.lns import is_lns_weight
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_lns_weight)
    return {weights.path_str(p): leaf for p, leaf in flat}


def _values(params) -> Dict[str, jax.Array]:
    """Every parameter leaf as float32 (packed words decoded)."""
    out = {}
    for name, leaf in _by_name(params).items():
        if hasattr(leaf, "packed"):
            out[name] = lnsgen.decode_words(leaf.packed, leaf.scale,
                                            leaf.fmt.bits, leaf.fmt.gamma)
        else:
            out[name] = leaf.astype(jnp.float32)
    return out


def grad_norms_from_state(state, beta: float):
    """Per-leaf norm of the step-1 gradient the optimizer received, read
    back from its second moment ``g2 = (1 - beta) g^2`` after one step."""
    return {k: jnp.sqrt(jnp.sum(v) / (1.0 - beta))
            for k, v in _by_name(state.opt.g2).items()}


def change_norms(params, params0):
    a, b = _values(params), _values(params0)
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three numbers: worst relative loss gap over the checked steps,
    and the worst leaf's gap between the two sides' norms of the step-1
    gradient and of the change after the checked steps, each against the
    larger of that leaf's reference norm and the median leaf's. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out (rounding moves them, not the gradient)."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"],
                                                       ref["loss"]))
    gmed = statistics.median(ref["grad_norm"].values())
    keep = [k for k, g in ref["grad_norm"].items() if g >= 1e-3 * gmed]

    def worst(key):
        med = statistics.median(ref[key][k] for k in keep)
        return max(abs(prog[key][k] - ref[key][k])
                   / max(ref[key][k], med, 1e-30) for k in keep)

    return {"loss_gap": loss_gap, "grad_norm_gap": worst("grad_norm"),
            "change_norm_gap": worst("change_norm")}


# ---------------------------------------------------------------------------
# the run


class TrainRun:
    """Set-up, window and check of one training cell run."""

    def __init__(self, cell: spec.Cell, seed: int, job: TrainJob = None,
                 **job_kw):
        self.cell = cell
        self.seed = seed
        self.words = lnsgen.seed_words(seed)
        self.job = job if job is not None else TrainJob(cell, **job_kw)
        self.readings: Dict = {}
        self.state = None
        self.steps_done = 0

    def _step(self, i: int):
        """One step through the window's own call and feed."""
        batch = self.job.batch_fn(self.words, jnp.int32(i))
        self.state, metrics = self.job.step_fn(self.state, batch)
        return metrics

    def setup(self) -> None:
        """Make the state, then drive the first steps: they compile the
        step and are the steps the reference checks."""
        job = self.job
        self.state = job.state_fn(self.words)
        params0 = jax.tree.map(jnp.copy, self.state.params)
        losses = []
        with jax.profiler.TraceAnnotation("bench.train_step"):
            for i in range(CHECK_STEPS):
                m = self._step(i)
                losses.append(float(m["loss"]))
                if i == 0:
                    g = jax.jit(grad_norms_from_state,
                                static_argnums=(1,))(self.state,
                                                     job.mcfg.beta)
                    grad = {k: float(v) for k, v in g.items()}
        ch = jax.jit(change_norms)(self.state.params, params0)
        del params0
        self.readings = {"loss": losses, "grad_norm": grad,
                         "change_norm": {k: float(v) for k, v in ch.items()}}
        self.steps_done = CHECK_STEPS

    def window(self, seconds: float, on_step=None) -> Dict[str, float]:
        """Whole steps until ``seconds`` have passed; the window ends with
        the last step's loss on the host."""
        steps = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                m = self._step(self.steps_done + steps)
                loss = float(m["loss"])
            steps += 1
            if on_step is not None:
                on_step(steps)
            if not math.isfinite(loss):
                raise FloatingPointError(f"loss {loss} at window step {steps}")
            t = time.perf_counter() - t0
            if t >= seconds:
                break
        self.steps_done += steps
        return {"steps": steps, "window_s": t,
                "tokens": steps * self.job.tokens_per_step}

    def free(self) -> None:
        """Drop the program's state (its device buffers with it)."""
        self.state = None
        gc.collect()

    def reference_readings(self) -> Dict:
        """The plain reference's three steps, at the numerics the
        configuration states (whatever variant the program ran)."""
        job = self.job
        words = jnp.asarray(self.words)
        batch_fn = jax.jit(
            lambda i: data.make_batch(words, i, batch=job.batch, seq=job.seq,
                                      vocab=job.dims.vocab, noise=job.noise))
        return self.cell.arch.train_readings(
            words, lambda i: batch_fn(jnp.int32(i)), job.dims,
            ref_spec(self.cell), steps=CHECK_STEPS)
