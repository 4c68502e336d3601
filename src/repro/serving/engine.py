"""Continuous-batching engine over the packed-LNS decode path.

The engine owns a fixed decode batch of ``num_slots`` rows. Each row is an
independent serving slot:

- the cache write cursor (``cache["idx"]``) is per-row, so a freed slot
  restarts at position 0 while its neighbours keep decoding;
- admission prefills the prompt through the *decode* path at batch 1 with
  the prompt right-padded to a shape bucket (a handful of jit entries,
  see ``_bucket``), then scatters the produced rows into the freed slot
  with the cursor rewound to the true prompt length;
- the decode step itself sees a single ``(num_slots, 1)`` shape forever:
  admitting a request never recompiles it (``decode_compiles`` stays 1);
- a finished sequence (EOS, ``max_new_tokens``, or cache capacity — the
  latter flagged ``truncated`` in its metrics) releases its slot and its
  KV is recycled by a later admission.

KV storage comes in two layouts (DESIGN.md §7.1):

- **dense** (default): one ``(num_slots, max_len)`` buffer per layer; slot
  count is capped by worst-case context.
- **paged** (``page_size=...``): full-context attention layers share one
  global pool of ``page_size``-token pages per layer plus per-slot block
  tables; ``num_slots`` can exceed what dense allocation permits and
  admission is gated by the ``BlockAllocator`` (pool exhausted -> the
  request waits in the queue, nothing wedges). ``alloc_policy`` picks how
  pages are claimed: ``"reserve"`` (default) takes the worst case
  ``ceil((prompt+budget)/page_size)`` up front — no preemption, but long
  budgets throttle concurrency; ``"ondemand"`` takes only the prompt's
  pages and grows the block table page by page as decode proceeds,
  preempting the *youngest* running request by recompute when the pool
  runs dry (its tokens are kept; re-admission re-prefills prompt +
  generated and resumes the sampling chain at the same event counter —
  delivered tokens are never re-emitted or re-drawn, and the stream
  stays identical up to float-level batch-composition effects: the
  quantized decode path scales activations per *tensor*, so a different
  set of co-resident rows can shift any row's logits by an ULP and flip
  a greedy near-tie). With ``prefix_cache`` the allocator keeps
  a chain hash over page-aligned prompt prefixes: a hit maps the resident
  pages into the new slot's block table and prefills only the suffix
  (copy-on-write on a partially-reused boundary page). Sliding-window
  rings, recurrent state, and MLA caches keep the dense layout; prefix
  reuse switches off unless every stateful layer is paged (those layers
  would otherwise never see the skipped tokens).

Weights stay in the packed 8-bit LNS wire format (``LNSWeight``) for the
whole request lifetime: routed GEMMs decode tile-locally through
``kernels/dispatch``, fallback leaves decode per layer inside the step —
the engine never materializes the tree and loads training checkpoints
with zero re-encoding (same bytes on disk, in the train state, and here).

Online serving: sampling runs **on device inside the jitted decode step**
— temperature / top-k / top-p / seed are per-slot ``(B,)`` batch inputs
(``repro.server.sampling``), so per-request settings never recompile and
only the sampled token ids cross to the host. Each appended token fires
``token_sink`` and each terminal transition fires ``finish_sink`` (the
gateway's stream hooks), ``abort()`` cancels a request mid-queue or
mid-flight (slot + KV pages released, co-batched rows undisturbed), and
``drain_finished()`` bounds the archives for long-lived callers.

Padding-safety: right-padded prefill is exact for *dense* attention caches
(the padded keys sit beyond the rewound cursor, masked and later
overwritten) and for *paged* pools (pad writes past a slot's page span are
dropped by the scatter; pads inside the span are masked and overwritten by
decode). It is NOT exact for recurrent state (Mamba/RWKV consume pad
tokens) nor for ring buffers shorter than the bucket (pads would wrap onto
live keys) — there the engine prefills at the exact prompt length instead:
correctness first, one extra compile per distinct length second.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.quantizer import QuantConfig
from repro.distributed.params_sharding import (cache_logical_axes,
                                               params_shardings,
                                               tree_shardings)
from repro.distributed.sharding import serving_rules, shard_ctx
from repro.kernels import dispatch
from repro.models.common import ArchConfig
from repro.models.model import forward, init_caches
from repro.obs.spans import Phases
from repro.optim.madam import MadamConfig
from repro.server.sampling import sample_logits, sampling_rows, set_row
from repro.serving.metrics import RequestMetrics, summarize
from repro.serving.request import Request, RequestQueue, RequestState
from repro.serving.scheduler import BlockAllocator, Scheduler
from repro.serving.spec import (SpecAutotuner, SpecConfig, build_draft_params,
                                request_class, spec_supported)
from repro.training.steps import build_decode_step

__all__ = ["Engine", "DEFAULT_BUCKETS", "ADMIT_FAIL_TRIP"]

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)

# consecutive admission failures after which step() stops isolating them
# and re-raises: one malformed request failing alone is serving as
# intended, but *every* admission failing means the engine itself is
# broken (device OOM, poisoned params) and masking that would keep
# /health green on a node that can no longer serve anything
ADMIT_FAIL_TRIP = 8

# layer kinds whose KV can live in a block-paged pool (full-context,
# non-MLA attention); everything else keeps the dense per-slot layout
_PAGED_KINDS = frozenset({"dense", "global", "moe", "shared_attn"})
_POOL_KEYS = ("kp", "vp", "kp_scale", "vp_scale")


def _set_cursor(caches, n):
    """Rewind every per-slot cache cursor in a (batch=1) cache tree to n."""
    def visit(path, leaf):
        if getattr(path[-1], "key", None) == "idx":
            return jnp.full_like(leaf, n)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, caches)


def _set_cursor_rows(caches, n):
    """Set every per-slot cache cursor to the per-row vector ``n`` (B,) —
    the speculative rewind: cursor leaves are (B,) or period-stacked
    (n_periods, B), both broadcast targets of a (B,) row vector."""
    def visit(path, leaf):
        if getattr(path[-1], "key", None) == "idx":
            return jnp.broadcast_to(n.astype(leaf.dtype), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, caches)


def _slot_scatter(b, m, slot):
    """Write the batch-1 leaf ``m`` into row ``slot`` of ``b`` — the slot
    axis is wherever the two shapes disagree (axis 0 for plain leaves,
    axis 1 for period-stacked ones)."""
    ax = next((i for i, (x, y) in enumerate(zip(b.shape, m.shape))
               if x != y), 0)
    start = [0] * b.ndim
    start[ax] = slot
    return jax.lax.dynamic_update_slice(b, m.astype(b.dtype), tuple(start))


class Engine:
    """Continuous-batching serving engine. See module docstring."""

    def __init__(
        self,
        cfg: ArchConfig,
        qcfg: Optional[QuantConfig],
        mcfg: Optional[MadamConfig],
        params: Any,
        *,
        num_slots: int = 4,
        max_len: int = 256,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        scan_unroll: int | bool = 1,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        alloc_policy: str = "reserve",
        speculate_k: int = 0,
        draft_bitwidth: int = 6,
        spec_autotune: bool = False,
        mesh=None,
        observer=None,
        checkpoint_id: Optional[str] = None,
    ):
        if alloc_policy not in ("reserve", "ondemand"):
            raise ValueError(f"alloc_policy must be 'reserve' or "
                             f"'ondemand', got {alloc_policy!r}")
        self.cfg, self.qcfg, self.mcfg = cfg, qcfg, mcfg
        # Mesh-native serving: one jax Mesh threaded from launch/serve.py
        # down to the kernels. Weights/KV shard per serving_rules (head- and
        # column-parallel where divisible, every contraction over a
        # replicated axis); activations and all host inputs replicate on the
        # batch axis, so the token stream stays token-for-token equal to the
        # single-device engine. The block allocator / prefix registry stay
        # host-side and mesh-wide: one logical block table, each shard
        # holding the page-local view of its own head group.
        self._mesh = mesh
        self._mesh_rules = serving_rules(cfg, mesh) if mesh is not None \
            else None
        self._repl = NamedSharding(mesh, PartitionSpec()) \
            if mesh is not None else None
        self.params = self._place_params(params)
        self.num_slots, self.max_len = num_slots, max_len
        self.buckets = tuple(sorted(b for b in buckets if b <= max_len))

        prefix, _, period = cfg.layer_pattern()
        kinds = set(prefix) | set(period)
        self._recurrent = bool(kinds & {"mamba", "rwkv"})
        self._window = cfg.sliding_window if "local" in kinds else None

        self._paged = bool(page_size) and not cfg.use_mla \
            and bool(kinds & _PAGED_KINDS)
        self.page_size = page_size if self._paged else None
        if self._paged:
            self._max_pages = -(-max_len // page_size)
            self.num_pages = num_pages or num_slots * self._max_pages
            self._null_page = self.num_pages
            # skipping re-prefill of a cached prefix is only sound when no
            # layer carries non-paged state that would miss those tokens
            self._prefix_ok = prefix_cache and kinds <= _PAGED_KINDS
        else:
            self.num_pages = 0
            self._prefix_ok = False
        self.alloc_policy = alloc_policy if self._paged else None
        self._ondemand = self._paged and alloc_policy == "ondemand"

        # self-speculative decoding (DESIGN.md §11): the draft model is a
        # low-bitwidth re-grid *view* of the serving weights, built lazily
        # per bitwidth in _draft_params. Sliding-window rings over-allocate
        # by k_max positions (window_slack) so a post-rejection rewind can
        # never have let the write head lap a maskable position.
        self.spec: Optional[SpecConfig] = None
        self._spec_slack = 0
        if speculate_k:
            reason = spec_supported(cfg)
            if reason is not None:
                raise ValueError(
                    f"speculative decoding unsupported here: {reason}")
            self.spec = SpecConfig(k=int(speculate_k),
                                   draft_bits=int(draft_bitwidth),
                                   autotune=bool(spec_autotune))
            self._spec_k_max = max(k for _, k in self.spec.arms()) \
                if spec_autotune else self.spec.k
            if self._window is not None:
                self._spec_slack = self._spec_k_max

        self._scan_unroll = scan_unroll
        decode = build_decode_step(cfg, qcfg, mcfg, scan_unroll=scan_unroll)
        self._decode_step = decode

        def decode_sample(params, caches, batch, pos, samp):
            # sampling fused into the decode jit: logits never leave the
            # device, only the (B,)/(B, K) token ids transfer
            logits, caches = decode(params, caches, batch, pos)
            return self._sample_impl(logits, samp), caches

        self._decode_fn = jax.jit(decode_sample, donate_argnums=(1,))
        self._sample_fn = jax.jit(self._sample_impl)  # prefill logits
        if self.spec is not None:
            # one fused launch per cycle: k draft decodes + the S=k verify
            # + accept/rewind. k is static (the draft loop unrolls in the
            # trace) and the draft tree's LNSFormat is static aux data, so
            # each (bits, k) arm compiles its own entry exactly once.
            self._spec_fn = jax.jit(self._spec_cycle_impl,
                                    static_argnames=("k",),
                                    donate_argnums=(2,))
            self._draft_views: Dict[int, Any] = {}
        # per-token / terminal event hooks (the gateway driver's taps);
        # called synchronously from step()/_admit() with (rid, token) and
        # (rid, reason, RequestState | None)
        self.token_sink: Optional[Callable[[int, Any], None]] = None
        self.finish_sink: Optional[
            Callable[[int, str, Optional[RequestState]], None]] = None
        # phase spans and counters (always on) and observability
        # (repro.obs.EngineObserver, DESIGN.md §13): every observer hook
        # site is a single `is not None` check
        self.phases = Phases(self._now)
        self.observer = observer
        # identity of the loaded weights, surfaced by /health; serving
        # launchers stamp it (checkpoint path / smoke-init tag)
        self.checkpoint_id = checkpoint_id
        # one fused call per admission: batch-1 prefill through the decode
        # path + scatter of the produced rows into the engine cache
        impl = self._prefill_paged_impl if self._paged else self._prefill_impl
        self._prefill_fn = jax.jit(impl, donate_argnums=(1,))
        if not self._paged:
            # zero batch-1 cache reused by every dense admission's prefill
            # (the jit body is functional, the template never mutates);
            # ring slack must match the engine cache or scatter shapes split
            self._mini_template = self._place_caches(
                init_caches(1, max_len, cfg, window_slack=self._spec_slack))

        self._reset_state()

    # -- mesh plumbing ------------------------------------------------------

    def _ctx(self):
        """Activate the serving mesh + rules for the enclosed jit call.

        Trace-time state (``shard()`` constraints, the dispatch layer's
        shard_map gate) reads a thread-local, and the driver runs the
        engine on its own thread — so every jit call site wraps itself
        instead of relying on whoever constructed the engine."""
        if self._mesh is None:
            return contextlib.nullcontext()
        return shard_ctx(self._mesh, self._mesh_rules)

    def _put(self, x, dtype=None):
        """Host input -> device, replicated across the mesh. Continuous
        batching feeds (B,)/(B, S) host arrays every step; replication is
        the layout ``device_put`` accepts for any B and the one the
        equality guarantee needs (no batch-sharded GEMM rows)."""
        if self._mesh is None:
            return jnp.asarray(x, dtype)
        return jax.device_put(np.asarray(x, dtype), self._repl)

    def _place_params(self, params):
        if self._mesh is None:
            return params
        with self._ctx():
            shardings = params_shardings(params, self._mesh,
                                         self._mesh_rules, serving=True)
        return jax.device_put(params, shardings)

    def _place_caches(self, caches):
        if self._mesh is None:
            return caches
        with self._ctx():
            shardings = tree_shardings(cache_logical_axes(caches),
                                       self._mesh, self._mesh_rules)
        return jax.device_put(caches, shardings)

    # -----------------------------------------------------------------------

    def _reset_state(self) -> None:
        cfg = self.cfg
        self.caches = self._place_caches(
            init_caches(self.num_slots, self.max_len, cfg,
                        page_size=self.page_size,
                        num_pages=self.num_pages or None,
                        window_slack=self._spec_slack))
        allocator = None
        if self._paged:
            allocator = BlockAllocator(self.num_pages, self.page_size)
            self._block_tables = np.full(
                (self.num_slots, self._max_pages), self._null_page, np.int32)
            self._slot_pages: List[Optional[List[int]]] = \
                [None] * self.num_slots
        self.scheduler = Scheduler(self.num_slots, allocator=allocator)
        self.queue = RequestQueue()
        # host mirrors of the in-graph per-slot cursors / last tokens
        self._slot_len = np.zeros((self.num_slots,), np.int64)
        tok_width = (cfg.num_codebooks,) if cfg.num_codebooks else ()
        self._last_tok = np.zeros((self.num_slots,) + tok_width, np.int32)
        # per-slot sampling params + sample-event counters (batch inputs
        # of the fused decode step; idle slots park at greedy)
        self._samp = sampling_rows(self.num_slots)
        self.completed: List[RequestMetrics] = []
        self.finished: List[RequestState] = []  # keeps generated tokens
        self.aborted: List[RequestState] = []   # cancelled mid-flight
        self._run_sink: Optional[List[RequestMetrics]] = None
        self.decode_steps = 0
        self.decode_rows = 0             # live rows summed over decode steps
        self.prefills = 0
        self.phases.clear()
        self.admit_failures = 0          # requests that blew up in _admit
        self._admit_fail_streak = 0      # consecutive; trips the engine
        self.prefill_tokens = 0          # padded tokens actually prefilled
        self.prefix_hits = 0             # admissions that reused pages
        self.prefix_reused_tokens = 0    # prompt tokens skipped via reuse
        # on-demand paging: states parked by preemption (rid -> state,
        # request itself waits at the queue head) and growth counters
        self._preempted: Dict[int, RequestState] = {}
        self.preemptions = 0             # recompute evictions under pressure
        self.decode_page_allocs = 0      # pages mapped mid-decode (ondemand)
        # speculative decoding counters (zeroed even when spec is off so
        # stats consumers can read them unconditionally)
        self.spec_cycles = 0             # fused draft+verify launches
        self.spec_draft_steps = 0        # draft decodes (k per cycle)
        self.spec_verify_steps = 0       # S=k verify passes (1 per cycle)
        self.spec_drafted = 0            # draft tokens scored (live slots)
        self.spec_accepted = 0           # drafts the target agreed with
        self.spec_emitted = 0            # tokens delivered by spec cycles
        self.spec_fallbacks = 0          # steps forced down the 1-token path
        self.spec_pages_trimmed = 0      # overshoot pages returned (ondemand)
        self._tuner: Optional[SpecAutotuner] = None
        if self.spec is not None:
            self._spec_arm = (self.spec.draft_bits, self.spec.k)
            if self.spec.autotune:
                self._tuner = SpecAutotuner(self.spec)
        # eager epoch: now() is read from other threads (online arrival
        # stamps) — lazy init would race the first step()'s _now()
        self._t0: Optional[float] = time.monotonic()

    @property
    def allocator(self) -> Optional[BlockAllocator]:
        return self.scheduler.allocator

    # ------------------------------------------------------------------
    # observability (only touched when an observer is attached)

    @property
    def observer(self):
        return self._observer

    @observer.setter
    def observer(self, observer) -> None:
        self._observer = observer
        self.phases.ring = observer.spans if observer is not None else None

    def attach_observer(self, observer) -> None:
        """Attach a ``repro.obs.EngineObserver`` (None detaches). Spans
        and timeline rows are stamped with the engine clock, so attach
        before (or at) the run whose events you want coherent."""
        self.observer = observer

    def phase_stats(self) -> Dict[str, Dict[str, float]]:
        """Per step phase (``engine.step``, ``engine.decode.wait``, ...):
        count, total and longest seconds since the last ``reset()``."""
        return self.phases.snapshot()

    def _obs_gauges(self) -> Dict[str, int]:
        """The step-timeline gauge row (allocator counts are O(1))."""
        alloc = self.allocator
        g = {"running": len(self.scheduler.running),
             "queued": len(self.queue),
             "preempts": self.preemptions}
        if alloc is not None:
            g["pages_free"] = alloc.free
            g["pages_cached"] = alloc.cached
        return g

    # ------------------------------------------------------------------
    # jitted bodies

    def _prefill_impl(self, params, big, mini, tokens, n, slot):
        """Dense-cache admission: batch-1 decode-path prefill of ``tokens``
        over the zero cache ``mini``, cursor rewound to the true prompt
        length ``n``, rows scattered into row ``slot`` of the engine cache
        ``big``. Returns (last-real-position logits, updated cache)."""
        out = forward(params, tokens, self.cfg, self.qcfg, caches=mini,
                      pos_offset=0)
        logits = jnp.take(out.logits, n - 1, axis=1)  # (1, V)
        filled = _set_cursor(out.caches, n)
        upd = lambda b, m: _slot_scatter(b, m, slot)
        return logits, jax.tree.map(upd, big, filled)

    def _prefill_paged_impl(self, params, big, tokens, n_new, n_cached,
                            n_total, table, cow_src, cow_dst, slot):
        """Paged admission, *in place* over the global pool: the pool
        leaves carry no batch axis, so the batch-1 suffix prefill attends
        and writes through the slot's real block table (``table``,
        (max_pages,) pool page ids) directly — prefix-cached pages are
        read where they live, never re-gathered into a scratch pool, and
        the fresh pages are written exactly once (the kernel's
        prefill-over-block-table path).

        The only page whose *content* must move is the copy-on-write
        boundary: ``cow_src`` (the shared page) is copied onto ``cow_dst``
        (the fresh copy, ``table[n_full]``) before the forward, so the
        suffix writes land on a page already holding the shared prefix
        tokens. Without a CoW boundary both ids name the null page — a
        self-copy of the sacrificial page, free and harmless.

        Suffix tokens prefill at ``pos_offset=n_cached``; bucket-padding
        writes past the prompt land in the slot's own still-unused
        positions or the null page (out-of-span writes drop). Dense
        (non-paged) layer rows and the cursor scatter into row ``slot``;
        the cursor rewinds to the true prompt length ``n_total``.
        Returns (last-real-position logits, updated cache)."""

        def mini_layer(c, stacked):
            if isinstance(c, dict) and "kp" in c:
                out = {}
                for k, v in c.items():
                    if k in _POOL_KEYS:  # shared leaf + the CoW page copy
                        out[k] = (v.at[:, cow_dst].set(v[:, cow_src])
                                  if stacked else
                                  v.at[cow_dst].set(v[cow_src]))
                    else:  # "idx": suffix prefill resumes at n_cached
                        shape = (v.shape[0], 1) if stacked else (1,)
                        out[k] = jnp.full(shape, n_cached, v.dtype)
                return out
            ax = 1 if stacked else 0  # zeros fold to constants inside jit
            return {k: jnp.zeros(v.shape[:ax] + (1,) + v.shape[ax + 1:],
                                 v.dtype) for k, v in c.items()}

        def map_tree(tree, fn):
            out: Dict[str, Any] = {}
            if "prefix" in tree:
                out["prefix"] = [fn(c, False) for c in tree["prefix"]]
            if "period" in tree:
                out["period"] = {k: fn(c, True)
                                 for k, c in tree["period"].items()}
            return out

        mini = map_tree(big, mini_layer)
        out = forward(params, tokens, self.cfg, self.qcfg, caches=mini,
                      pos_offset=n_cached, block_tables=table[None])
        logits = jnp.take(out.logits, n_new - 1, axis=1)  # (1, V)
        filled = _set_cursor(out.caches, n_total)

        def scatter_layer(b, m, stacked):
            if isinstance(b, dict) and "kp" in b:
                out = {}
                for k in b:
                    if k in _POOL_KEYS:
                        # the mini leaf IS the updated global pool (the
                        # forward wrote through the real page ids)
                        out[k] = m[k]
                    else:
                        out[k] = _slot_scatter(b[k], m[k], slot)
                return out
            return jax.tree.map(lambda x, y: _slot_scatter(x, y, slot), b, m)

        def zip_tree(btree, mtree):
            out: Dict[str, Any] = {}
            if "prefix" in btree:
                out["prefix"] = [scatter_layer(x, y, False) for x, y in
                                 zip(btree["prefix"], mtree["prefix"])]
            if "period" in btree:
                out["period"] = {k: scatter_layer(btree["period"][k],
                                                  mtree["period"][k], True)
                                 for k in btree["period"]}
            return out

        return logits, zip_tree(big, filled)

    def _spec_cycle_impl(self, dparams, params, caches, last_tok, pos, samp,
                         block_tables, *, k):
        """One fused speculative cycle (DESIGN.md §11), a single jit:

        1. k greedy S=1 draft decodes with the re-grid view ``dparams``,
           advancing the per-row cursors pos -> pos+k (draft KV written by
           the *target-precision* cache path — the draft only changes the
           weights the logits come from, never the cache contents, so an
           accepted position's KV is exactly what the baseline engine
           would have written for that token);
        2. cursor rewind to ``pos`` and one S=k verify ``forward`` with
           the full-precision weights over [last_tok, draft[:, :-1]] —
           position j's logits condition on the same prefix the baseline
           would see when sampling its (step+j)-th token;
        3. per-position target sampling with the fold counter offset by j
           (``sample_logits(step_offset=j)`` — seeded chains replay
           token-for-token), the longest-agreeing-prefix accept rule, and
           an in-graph rewind of every cursor to pos+m.

        Returns ``(s, acc, m, caches)``: the target's samples (B, k), the
        accepted-draft count (B,), and the emitted count ``m = min(acc+1,
        k)`` — the bonus +1 is the target's own sample at the first
        disagreement (or the run's end), which is always correct to emit.
        Rejected writes at positions >= pos+m are dead: cursors moved
        back, so they are masked everywhere and overwritten before those
        positions ever become attendable again.
        """
        decode = self._decode_step
        cur = last_tok
        drafts = []
        for j in range(k):  # static unroll: one launch, no host ping-pong
            batch = {"tokens": cur[:, None]}
            if block_tables is not None:
                batch["block_tables"] = block_tables
            logits, caches = decode(dparams, caches, batch, pos + j)
            cur = dispatch.fused_sample(logits.astype(jnp.float32), None, None)
            drafts.append(cur)
        draft = jnp.stack(drafts, axis=1)                       # (B, k)

        caches = _set_cursor_rows(caches, pos)
        x = jnp.concatenate([last_tok[:, None], draft[:, :-1]], axis=1)
        out = forward(params, x, self.cfg, self.qcfg, caches=caches,
                      pos_offset=pos, block_tables=block_tables,
                      scan_unroll=self._scan_unroll)
        caches = out.caches
        s = jnp.stack([self._sample_impl(out.logits[:, j], samp,
                                         step_offset=j)
                       for j in range(k)], axis=1)              # (B, k)

        eq = (draft == s).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)          # (B,)
        m = jnp.minimum(acc + 1, k)
        caches = _set_cursor_rows(caches, pos + m)
        return s, acc, m, caches

    def _draft_params(self, bits: int):
        """The (cached) draft view at ``bits`` wire bits — shared scales,
        shared non-LNS leaves; bits == serving bits returns the target
        tree itself (the identity arm: every draft accepts)."""
        view = self._draft_views.get(bits)
        if view is None:
            view = build_draft_params(self.params, bits)
            self._draft_views[bits] = view
            self._numerics_cache = None  # new view -> re-derive health
        return view

    def numerics_snapshot(self) -> Dict[str, Any]:
        """Per-tree LNS numerics health for ``/health`` (DESIGN.md §14).

        Weight-tree code-rail occupancy (the live-weights readiness
        signal: codes piling at either rail mean the serving copy lost
        resolution) plus the re-grid error of every built draft view.
        Computed lazily and cached — invalidated when a new draft view is
        built (and, later, when live-weight swaps land), so the driver's
        stats refresh never re-reduces the tree.
        """
        cached = getattr(self, "_numerics_cache", None)
        if cached is not None:
            return cached
        from repro.obs.numerics import tree_code_stats
        from repro.serving.spec import draft_requant_error
        snap: Dict[str, Any] = {"weights": tree_code_stats(self.params)}
        drafts = {}
        for bits, view in sorted(getattr(self, "_draft_views", {}).items()):
            if view is self.params:
                continue
            drafts[f"b{bits}"] = draft_requant_error(self.params, view)
        if drafts:
            snap["draft_requant"] = drafts
        self._numerics_cache = snap
        return snap

    # ------------------------------------------------------------------
    # shape bucketing

    def _bucket(self, plen: int) -> int:
        assert plen <= self.max_len  # guaranteed by submit()
        if self._recurrent:
            return plen  # pads would pollute the recurrent state
        for b in self.buckets:
            if b >= plen and (self._window is None or b <= self._window):
                return b
        return plen  # no safe bucket: exact shape (ring wrap / long prompt)

    @property
    def prefill_compiles(self) -> int:
        return self._prefill_fn._cache_size()

    @property
    def decode_compiles(self) -> int:
        return self._decode_fn._cache_size()

    # ------------------------------------------------------------------
    # lifecycle

    def reset(self) -> None:
        """Clear all request/slot state but keep the compiled steps — a
        reset engine re-runs a trace with warm jit caches (benchmarks)."""
        self._reset_state()

    def validate(self, prompt: Sequence, max_new_tokens: int = 0) -> None:
        """Raise ValueError if this request can *never* be hosted: prompt
        beyond the cache, page demand beyond the pool, or a prompt whose
        shape doesn't fit the model (flat ids vs per-codebook rows, wrong
        row width — those would otherwise blow up inside the prefill jit
        at admission time). The one admission formula, shared by
        ``submit()`` and the online gateway's pre-flight check (a 400,
        not backpressure)."""
        try:
            arr = np.asarray(prompt)
        except ValueError:
            raise ValueError("prompt rows must share one shape") from None
        if arr.size and not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(f"prompt must hold integer token ids, got "
                             f"dtype {arr.dtype}")
        k = self.cfg.num_codebooks
        if k:
            if arr.ndim != 2 or arr.shape[1] != k:
                raise ValueError(f"model expects prompt shape (len, {k}) — "
                                 f"one id row per codebook — got "
                                 f"{arr.shape}")
        elif arr.ndim != 1:
            raise ValueError(f"model expects a flat list of token ids, got "
                             f"shape {arr.shape}")
        prompt_len = arr.shape[0]
        if prompt_len < 1:
            raise ValueError("prompt must hold at least one token")
        lo, hi = int(arr.min()), int(arr.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            # without this, out-of-range ids are silently clamped by the
            # embedding gather under jit and decode 200s with garbage
            raise ValueError(f"prompt token ids must be in [0, "
                             f"{self.cfg.vocab_size}), got [{lo}, {hi}]")
        if prompt_len > self.max_len:
            raise ValueError(f"prompt len {prompt_len} exceeds engine "
                             f"max_len {self.max_len}")
        if self._paged:
            need = self._pages_for(prompt_len, max_new_tokens)
            if need > self.num_pages:
                raise ValueError(f"needs {need} KV pages, pool holds "
                                 f"{self.num_pages}")

    def submit(self, req: Request) -> None:
        # reject before any slot is bound: failing later (inside _admit)
        # would leak the already-occupied slot and wedge the engine.
        # The online driver validates at its pre-flight (same formula)
        # and marks the request, so the O(prompt) scan isn't paid twice
        if not getattr(req, "_prevalidated", False):
            try:
                self.validate(req.prompt, req.max_new_tokens)
            except ValueError as e:
                raise ValueError(f"request {req.rid}: {e}") from None
        self.queue.push(req)

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = time.monotonic()
        return time.monotonic() - self._t0

    def now(self) -> float:
        """Engine-clock timestamp (seconds since first use) — online
        callers stamp ``Request.arrival`` with this so queue-wait and
        TTFT share the engine's timebase."""
        return self._now()

    def _sample_impl(self, logits, samp, step_offset=None):
        """On-device sampler body (jitted standalone for prefill logits,
        inlined into the decode jit for the hot loop; ``step_offset``
        shifts the fold counter for the speculative verify positions)."""
        with jax.named_scope("sample"):
            return sample_logits(logits, samp,
                                 num_codebooks=self.cfg.num_codebooks,
                                 vocab_size=self.cfg.vocab_size,
                                 step_offset=step_offset)

    def _samp_row(self, slot: int) -> Dict[str, jax.Array]:
        """Batch-1 view of one slot's sampling params (prefill sample)."""
        return {k: self._put(v[slot:slot + 1])
                for k, v in self._samp.items()}

    # ------------------------------------------------------------------
    # paged admission bookkeeping (host side)

    def _pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case pages a request holds: its prompt plus the budget's
        decode writes (the final token is returned but never cached)."""
        n_pos = min(prompt_len + max(max_new_tokens - 1, 0), self.max_len)
        return -(-n_pos // self.page_size)

    def _pages_needed(self, req: Request) -> int:
        return self._pages_for(req.prompt_len, req.max_new_tokens)

    def _reserve_pages(self, req: Request,
                       n_tokens: Optional[int] = None
                       ) -> Optional[Dict[str, Any]]:
        """Match the prompt's cached prefix and reserve this request's
        pages; None (nothing held) if the pool can't host it right now.

        ``n_tokens`` is the number of positions admission will prefill —
        the prompt length, except when resuming a preempted request
        (prompt plus the tokens generated before eviction). Under the
        ``reserve`` policy the whole worst-case budget is taken up front;
        under ``ondemand`` only the prefill's pages are taken and decode
        grows the block table page by page (``_grow_decode_pages``).

        Under pressure the match degrades before the reservation fails:
        first the copy-on-write hold goes (it transiently pins one page
        beyond the request's own demand — on a pool sized exactly at
        ``_pages_needed`` that hold would otherwise wedge the identical
        reservation forever), then the whole prefix match (releasing the
        shared pages back to the evictable set, so any request ``submit``
        accepted can always be hosted with zero reuse once slots drain)."""
        alloc = self.allocator
        page = self.page_size
        plen = req.prompt_len if n_tokens is None else n_tokens
        need = -(-plen // page) if self._ondemand \
            else self._pages_needed(req)
        keys: List[bytes] = []
        matched: List[int] = []
        if self._prefix_ok:
            # memoized on the request (an exhausted pool retries this
            # reservation every step), tagged with the page size — one
            # trace may be replayed through engines with different pages
            memo = getattr(req, "_chain_keys", None)
            if memo is None or memo[0] != page:
                memo = (page, BlockAllocator.chain_keys(req.prompt, page))
                req._chain_keys = memo
            keys = memo[1]
            matched = alloc.match(keys)
        # always recompute at least the last prompt token (its logits seed
        # decoding), so reuse is capped at plen - 1
        n_cached = min(len(matched) * page, plen - 1)
        n_full = n_cached // page
        cow = matched[n_full] if n_cached % page else None
        shared = matched[:n_full]
        alloc.retain(shared)
        if cow is not None:
            alloc.retain([cow])
        fresh = alloc.alloc(need - n_full)
        if fresh is None and cow is not None:
            alloc.release([cow])           # forfeit the boundary reuse
            cow = None
            n_cached = n_full * page
            fresh = alloc.alloc(need - n_full)
        if fresh is None and shared:
            alloc.release(shared)          # forfeit the prefix match
            shared, n_full, n_cached = [], 0, 0
            fresh = alloc.alloc(need)
        if fresh is None:                  # genuine pressure: nothing held
            return None
        return {"n_cached": n_cached, "n_full": n_full, "cow": cow,
                "shared": shared, "fresh": fresh, "keys": keys}

    # ------------------------------------------------------------------
    # on-demand paging: decode-time growth + preemption by recompute

    @staticmethod
    def _age(rs: RequestState):
        """Total order on running requests, oldest first (victim choice
        and growth priority must agree, or progress isn't guaranteed)."""
        return (rs.t_admit, rs.request.arrival, rs.request.rid)

    def _preempt(self, rs: RequestState) -> None:
        """Evict a running request to reclaim its pages: slot, sampler
        row, and pages are released; the state parks in ``_preempted``
        and the request returns to the head of the queue. Re-admission
        recomputes the evicted KV (``_admit`` resume path) — tokens
        already delivered are never re-emitted or re-drawn."""
        self.preemptions += 1
        self._preempted[rs.request.rid] = rs
        self._release_slot(rs)
        self.queue.requeue(rs.request)
        if self.observer is not None:
            self.observer.preempted(rs, self._now())

    def _write_span(self, rs: RequestState, lookahead: int) -> tuple:
        """The position span ``[n, last]`` the next ``lookahead`` decode
        writes may touch for ``rs`` — clamped to the request's own page
        demand (``_pages_for``'s formula: the final budgeted token is
        returned but never cached) so speculative lookahead never maps
        pages the request cannot use. The immediate next write position
        is always in the span (the baseline single-token step)."""
        n = int(self._slot_len[rs.slot])
        req = rs.request
        limit = min(req.prompt_len + max(req.max_new_tokens - 1, 0),
                    self.max_len)
        return n, min(n + lookahead, max(limit, n + 1)) - 1

    def _grow_decode_pages(self, lookahead: int = 1) -> None:
        """Map fresh pages onto every running slot whose next ``lookahead``
        decode writes cross into unmapped territory (``ondemand`` policy:
        the admission reservation covered only the prefill; a speculative
        cycle asks for its whole k-token span up front). Under pool
        exhaustion the *youngest* running request yields (preemption by
        recompute) until the allocation succeeds — the oldest running
        request is never a victim, so FCFS progress is guaranteed."""
        page = self.page_size
        for rs in sorted(self.scheduler.running.values(), key=self._age):
            if self.scheduler.running.get(rs.slot) is not rs:
                continue  # evicted by an older slot's growth this step
            n, last = self._write_span(rs, lookahead)
            bt = self._block_tables[rs.slot]
            for pi in range(n // page,
                            min(last // page, self._max_pages - 1) + 1):
                if bt[pi] != self._null_page:
                    continue
                got = self.allocator.alloc(1)
                while got is None:
                    victim = max(
                        (v for v in self.scheduler.running.values()
                         if v is not rs), key=self._age, default=None)
                    if victim is None:
                        victim = rs  # alone and still starved: yield fully
                    self._preempt(victim)
                    if victim is rs:
                        break
                    got = self.allocator.alloc(1)
                if got is None:
                    break  # rs evicted itself; its row idles this step
                bt[pi] = got[0]
                self._slot_pages[rs.slot].append(got[0])
                self.decode_page_allocs += 1

    def _trim_overshoot(self, rs: RequestState) -> None:
        """Return a slot's overshoot pages after a speculative cycle: any
        page wholly beyond the next write position was mapped for draft
        tokens the verify rejected. Only decode-growth pages live out
        there (prefix/prefill pages all sit at or below the cursor's
        page), so the release can never touch a shared or registered
        page."""
        page = self.page_size
        keep = int(self._slot_len[rs.slot]) // page  # next-write page
        bt = self._block_tables[rs.slot]
        pages = self._slot_pages[rs.slot]
        for pi in range(keep + 1, self._max_pages):
            pid = int(bt[pi])
            if pid == self._null_page:
                continue
            self.allocator.release([pid])
            pages.remove(pid)
            bt[pi] = self._null_page
            self.spec_pages_trimmed += 1

    # ------------------------------------------------------------------
    # admission / decode

    def _admit(self, rs: RequestState, clock,
               resv: Optional[Dict[str, Any]] = None):
        """Prefill ``rs`` into its slot and emit its first token (or, for
        a preempted request, recompute its evicted KV). Returns the
        ``engine.prefill`` span."""
        req = rs.request
        g = len(rs.generated)
        # resuming a preempted request (ondemand policy): recompute the
        # evicted KV by prefilling the prompt plus every already delivered
        # token except the last (which seeds decoding and, like any fresh
        # prefill's sampled token, is never cached)
        plen = req.prompt_len + max(g - 1, 0)
        n_cached = resv["n_cached"] if self._paged else 0
        bucket = self._bucket(plen - n_cached)
        with self.phases.span("engine.prefill", rid=req.rid, bucket=bucket,
                              n_cached=n_cached) as sp:
            prompt = np.asarray(req.prompt, np.int32)
            if g:
                tail = np.asarray(rs.generated[:-1], np.int32).reshape(
                    (-1,) + prompt.shape[1:])
                prompt = np.concatenate([prompt, tail])
            if self._paged:
                held = resv["shared"] + resv["fresh"]
                n_pages = resv["n_full"] + len(resv["fresh"])
                bt = np.full((self._max_pages,), self._null_page, np.int32)
                bt[:resv["n_full"]] = resv["shared"]
                bt[resv["n_full"]:n_pages] = resv["fresh"]
                # copy-on-write boundary: the shared page's content is copied
                # onto its fresh twin inside the prefill jit; null -> null
                # (a free self-copy of the sacrificial page) when absent
                if resv["cow"] is not None:
                    cow_src, cow_dst = resv["cow"], int(bt[resv["n_full"]])
                else:
                    cow_src = cow_dst = self._null_page
                n_new = plen - n_cached
                tokens = np.zeros((1, bucket) + prompt.shape[1:], np.int32)
                tokens[0, :n_new] = prompt[n_cached:]
                with self._ctx():
                    logits, self.caches = self._prefill_fn(
                        self.params, self.caches, self._put(tokens),
                        self._put(n_new, jnp.int32),
                        self._put(n_cached, jnp.int32),
                        self._put(plen, jnp.int32),
                        self._put(bt), self._put(cow_src, jnp.int32),
                        self._put(cow_dst, jnp.int32),
                        self._put(rs.slot, jnp.int32))
                if resv["cow"] is not None:  # content copied; drop the hold
                    self.allocator.release([resv["cow"]])
                    resv["cow"] = None  # a later unwind must not re-release
                if self._prefix_ok:  # publish this prompt's full pages
                    # keys cover the *original* prompt only — resumed tokens
                    # are generated content, never prefix-cache material
                    for i, key in enumerate(resv["keys"]):
                        self.allocator.register(key, int(bt[i]))
                self._block_tables[rs.slot] = bt
                self._slot_pages[rs.slot] = held
                if n_cached:
                    self.prefix_hits += 1
                    self.prefix_reused_tokens += n_cached
            else:
                tokens = np.zeros((1, bucket) + prompt.shape[1:], np.int32)
                tokens[0, :plen] = prompt
                with self._ctx():
                    logits, self.caches = self._prefill_fn(
                        self.params, self.caches, self._mini_template,
                        self._put(tokens), self._put(plen, jnp.int32),
                        self._put(rs.slot, jnp.int32))

            set_row(self._samp, rs.slot, req.sampling)  # sample event 0
            if g:
                # every emitted token was already delivered; the last one
                # seeds decoding and the sampling chain resumes at event g —
                # same seed, same counter, so no token is ever re-drawn
                # (logits can still move by an ULP vs the unpreempted run:
                # per-tensor activation scales couple co-resident rows)
                tok = np.asarray(rs.generated[-1], np.int32)
                self._samp["step"][rs.slot] = g
            else:
                with self._ctx(), self.phases.span("engine.prefill.wait"):
                    tok = np.asarray(
                        self._sample_fn(logits, self._samp_row(rs.slot)))[0]
                self._samp["step"][rs.slot] = 1
            self.prefills += 1
            self.prefill_tokens += bucket
            self._slot_len[rs.slot] = plen
            self._last_tok[rs.slot] = tok
            if not g:
                rs.generated.append(tok.tolist() if tok.ndim else int(tok))
                rs.t_first_token = clock()
                if self.token_sink is not None:
                    self.token_sink(req.rid, rs.generated[-1])
            self._maybe_finish(rs, clock)
        return sp

    def _maybe_finish(self, rs: RequestState, clock) -> None:
        # the cursor names the *next* write position: the slot is out of
        # capacity only once it passes max_len - 1 (position max_len - 1
        # itself is usable — finishing one step earlier wasted it)
        full = self._slot_len[rs.slot] >= self.max_len
        if rs.done or full:
            budget = len(rs.generated) >= rs.request.max_new_tokens
            reason = ("stop" if rs.hit_stop else
                      "length" if budget else "capacity")
            self._finish(rs, clock, reason)

    def _release_slot(self, rs: RequestState) -> None:
        """Free a terminal request's slot, sampler row, and (paged) the
        pages recorded on the slot."""
        self.scheduler.release(rs.slot)
        set_row(self._samp, rs.slot, None)  # idle slots sample greedy
        if self._paged:
            pages = self._slot_pages[rs.slot]
            if pages:
                self.allocator.release(pages)
            self._slot_pages[rs.slot] = None
            # stale decode writes from the recycled row must land in
            # the null page, never in someone else's live pages
            self._block_tables[rs.slot] = self._null_page

    def _finish(self, rs: RequestState, clock, reason: str) -> None:
        """Terminal transition: stamp the state, release the slot and its
        KV pages, archive, and fire ``finish_sink``."""
        rs.t_finish = clock()
        rs.finish_reason = reason
        self._release_slot(rs)
        if reason == "aborted":
            self.aborted.append(rs)
        else:
            self.finished.append(rs)
            m = RequestMetrics.from_state(rs, truncated=reason == "capacity")
            self.completed.append(m)
            if self._run_sink is not None:
                self._run_sink.append(m)
        if self.finish_sink is not None:
            self.finish_sink(rs.request.rid, reason, rs)
        if self.observer is not None:
            self.observer.finished(rs, reason)

    def _cache_poisoned(self) -> bool:
        """True when a failed donated call consumed the cache buffers."""
        return any(getattr(leaf, "is_deleted", None) and leaf.is_deleted()
                   for leaf in jax.tree.leaves(self.caches))

    def _archive_error(self, rs: RequestState) -> None:
        """Shared tail of both admission-failure paths: stamp, count,
        archive, and fire the terminal event. Slot/page unwinding stays
        caller-side — the reservation path never bound a slot."""
        rs.finish_reason = "error"
        self.admit_failures += 1
        self._admit_fail_streak += 1
        self.aborted.append(rs)
        if self.finish_sink is not None:
            self.finish_sink(rs.request.rid, "error", rs)

    def _fail_admission(self, rs: RequestState, resv: Optional[Dict],
                        clock) -> None:
        """Unwind a failed ``_admit``: free the slot, return the page
        reservation (wherever the failure left it), archive the state
        with reason "error", and fire ``finish_sink`` so an online
        caller's stream terminates instead of hanging."""
        rs.t_finish = clock()
        # pages recorded on the slot (failure after _admit's bookkeeping,
        # cow already dropped) are released by the shared teardown; a
        # failure before that point leaves the reservation ours to return
        recorded = self._paged and self._slot_pages[rs.slot] is not None
        self._release_slot(rs)
        if self._paged and not recorded and resv is not None:
            self.allocator.release(resv["shared"] + resv["fresh"])
            if resv["cow"] is not None:
                self.allocator.release([resv["cow"]])
        self._archive_error(rs)

    def abort(self, rid: int, now: Optional[float] = None) -> bool:
        """Cancel a request mid-queue, mid-prefill, or mid-decode.

        A queued request is simply dropped; a running one releases its
        slot and (paged mode) its KV pages immediately — refcounts return
        to baseline and the co-batched rows never see a perturbation
        (their cache rows, cursors, and sampling chains are untouched).
        Returns False if ``rid`` is not live here (already finished or
        never submitted) — aborts are naturally racy, callers shouldn't
        treat that as an error."""
        clock = self._now if now is None else (lambda: now)
        req = self.queue.remove(rid)
        if req is not None:
            # a preempted request waits in the queue with its state
            # parked; its pages were already released at eviction
            rs = self._preempted.pop(rid, None)
            if rs is not None:
                rs.t_finish = clock()
                rs.finish_reason = "aborted"
                self.aborted.append(rs)
            if self.finish_sink is not None:
                self.finish_sink(rid, "aborted", rs)
            if self.observer is not None:
                self.observer.aborted_queued(rid, clock())
            return True
        for rs in self.scheduler.running.values():
            if rs.request.rid == rid:
                self._finish(rs, clock, "aborted")
                return True
        return False

    # ------------------------------------------------------------------
    # speculative decoding (host side)

    def _spec_ready(self, k: int) -> bool:
        """Every running slot can host a k-token speculative span: the
        dense row-insert must not clamp at capacity, and (paged) every
        page a surviving write could land in must be mapped — a dropped
        write is only safe past the request's own budget limit."""
        for rs in self.scheduler.running.values():
            n = int(self._slot_len[rs.slot])
            if n + k > self.max_len:
                return False
            if self._paged:
                page = self.page_size
                _, last = self._write_span(rs, k)
                bt = self._block_tables[rs.slot]
                for pi in range(n // page, last // page + 1):
                    if bt[pi] == self._null_page:
                        return False
        return True

    def _spec_step(self, clock, k: int) -> None:
        """Run one fused speculative cycle and apply its outcome on the
        host: emit the accepted run (plus the verify's bonus token) per
        live slot, advance the cursor/sampler mirrors by the emitted
        count, finish any terminal transition inside the run, and return
        overshoot pages. Every emitted token is the *target* model's own
        sample at the correct fold counter, so the stream is
        token-for-token the baseline engine's (see DESIGN.md §11 for the
        per-tensor activation-scale ULP caveat)."""
        bits, _ = self._spec_arm
        obs = self.observer
        with self.phases.span("engine.spec", k=k) as sp:
            pos0 = self._slot_len.copy()
            batch_bt = self._put(self._block_tables) if self._paged else None
            samp = {kk: self._put(v) for kk, v in self._samp.items()}
            with self._ctx():
                s_dev, acc_dev, m_dev, self.caches = self._spec_fn(
                    self._draft_params(bits), self.params, self.caches,
                    self._put(self._last_tok), self._put(pos0, jnp.int32),
                    samp, batch_bt, k=k)
            with self.phases.span("engine.spec.wait"):
                s = np.array(s_dev)
                acc = np.array(acc_dev)
                m = np.array(m_dev).astype(np.int64)
            self._admit_fail_streak = 0
            self.spec_cycles += 1
            self.spec_draft_steps += k
            self.spec_verify_steps += 1
            # per-row mirrors advance by the emitted count — idle rows too
            # (they drafted greedily into dead rows, exactly as the
            # baseline step advances every row by 1)
            self._slot_len = pos0 + m
            self._samp["step"] += m.astype(np.int32)
            self._last_tok = s[np.arange(self.num_slots),
                               m - 1].astype(np.int32)
            emitted_total = 0
            per_class: Dict[str, Any] = {}
            obs_rows: Optional[List] = [] if obs is not None else None
            for slot, rs in list(self.scheduler.running.items()):
                a = int(acc[slot])
                if obs_rows is not None:
                    obs_rows.append((rs.request.rid, a, int(m[slot])))
                self.spec_drafted += k
                self.spec_accepted += a
                rs.spec_cycles += 1
                rs.spec_drafted += k
                rs.spec_accepted += a
                if self._tuner is not None:
                    cls = request_class(rs.request)
                    ca, cd = per_class.get(cls, (0, 0))
                    per_class[cls] = (ca + a, cd + k)
                for j in range(int(m[slot])):
                    rs.generated.append(int(s[slot, j]))
                    emitted_total += 1
                    if self.token_sink is not None:
                        self.token_sink(rs.request.rid, rs.generated[-1])
                    if rs.done:
                        # a stop/budget transition inside the accepted run
                        # is terminal — the run's later tokens were never
                        # part of the baseline stream and are dropped
                        # unemitted (the cursor overshoot is moot: the
                        # slot releases below)
                        break
                self._maybe_finish(rs, clock)
                if self._ondemand and self.scheduler.running.get(slot) is rs:
                    self._trim_overshoot(rs)
            self.spec_emitted += emitted_total
        if obs is not None:
            obs.spec_cycle(sp.t0, sp.t1, k=k, rows=obs_rows,
                           emitted=emitted_total,
                           gauges=self._obs_gauges())
        if self._tuner is not None:
            self._tuner.observe(self._spec_arm, emitted_total,
                                sp.t1 - sp.t0, per_class)
            self._spec_arm = self._tuner.propose()

    @property
    def spec_accept_rate(self) -> float:
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    def spec_snapshot(self) -> Optional[Dict[str, Any]]:
        """Flat JSON-safe dict of speculative-decoding state for
        ``/metrics`` (None when speculation is off)."""
        if self.spec is None:
            return None
        snap: Dict[str, Any] = {
            "spec_cycles": self.spec_cycles,
            "spec_draft_steps": self.spec_draft_steps,
            "spec_verify_steps": self.spec_verify_steps,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_emitted": self.spec_emitted,
            "spec_fallbacks": self.spec_fallbacks,
            "spec_pages_trimmed": self.spec_pages_trimmed,
            "spec_accept_rate": round(self.spec_accept_rate, 4),
            "spec_draft_bits": self._spec_arm[0],
            "spec_k": self._spec_arm[1],
        }
        if self._tuner is not None:
            snap.update(self._tuner.snapshot())
        return snap

    def step(self, now: Optional[float] = None) -> bool:
        """Admit ready requests, then advance every occupied slot one
        token. Returns False when there was nothing to do.

        With an explicit ``now`` (simulated-time replay) every timestamp
        this step produces uses that value, so TTFT/latency stay in the
        caller's clock; otherwise the engine's monotonic clock is read at
        each event.

        Each phase runs inside a span (``self.phases``) that is a child of
        ``engine.step``: host time under ``engine.step`` alone is time no
        phase claims."""
        with self.phases.span("engine.step"):
            return self._step(now)

    def _step(self, now: Optional[float]) -> bool:
        clock = self._now if now is None else (lambda: now)
        obs = self.observer
        span = self.phases.span
        while self.scheduler.has_free():
            req = self.queue.pop_ready(clock())
            if req is None:
                break
            with span("engine.admit", rid=req.rid,
                      prompt_len=req.prompt_len):
                resv = None
                resume = self._preempted.get(req.rid) if self._paged \
                    else None
                if self._paged:
                    n_tok = req.prompt_len
                    if resume is not None:
                        n_tok += len(resume.generated) - 1
                    try:
                        resv = self._reserve_pages(req, n_tok)
                    except Exception:
                        # a prompt the reservation can't even hash
                        # (slipped past validate()) fails alone, before
                        # slot binding; archive an "error" state (slot
                        # -1: never bound) so offline callers' accounting
                        # still balances
                        rs = RequestState(request=req, slot=-1,
                                          t_admit=clock())
                        rs.t_finish = clock()
                        self._archive_error(rs)
                        if self._admit_fail_streak >= ADMIT_FAIL_TRIP:
                            raise
                        continue
                    if resv is None:  # pool exhausted: wait for a release
                        self.queue.requeue(req)
                        break
                rs = self.scheduler.admit(req, clock())
                if resume is not None:
                    # continuity across preemption: same token list (the
                    # resume prefill keys off it) and original timestamps,
                    # so TTFT/latency metrics span the whole request
                    del self._preempted[req.rid]
                    rs.generated = resume.generated
                    rs.t_admit = resume.t_admit
                    rs.t_first_token = resume.t_first_token
            try:
                sp = self._admit(rs, clock, resv)
                self._admit_fail_streak = 0
                if obs is not None:
                    obs.admitted(rs, resumed=resume is not None)
                    obs.prefill(rs, sp.t0, sp.t1, gauges=self._obs_gauges())
            except Exception:
                # a request that blows up inside admission (a shape that
                # slipped past validate(), a prefill-time failure) must
                # fail alone: release its slot and reservation, fire its
                # terminal event, and keep serving the co-batched rows —
                # one malformed request must not take down the engine.
                # Unless *every* admission is failing: then the engine
                # itself is broken and the fault must propagate (503),
                # not hide behind per-request errors.
                if rs.finish_reason is not None:
                    # the request already reached its terminal transition
                    # inside _admit (1-token / instant-stop finish) and
                    # the raise came *after* it (e.g. a sink tap) —
                    # teardown already ran, unwinding again would
                    # double-release pages held by live neighbours
                    raise
                if self._cache_poisoned():
                    # the prefill jit donates self.caches: an
                    # *execution*-time failure (device OOM on an
                    # accelerator) consumed the donated buffers, so the
                    # co-batched rows are gone too — isolation would be
                    # a lie and the next decode step would die with a
                    # confusing "Array deleted"; fail now, with the
                    # real cause (trace-time failures — the bad-shape
                    # class — never execute, so the cache stays live
                    # and those are genuinely isolated)
                    raise
                self._fail_admission(rs, resv, clock)
                if self._admit_fail_streak >= ADMIT_FAIL_TRIP:
                    raise
        spec_k = self._spec_arm[1] if self.spec is not None else 0
        if self._ondemand:
            with span("engine.grow_pages"):
                self._grow_decode_pages(lookahead=max(spec_k, 1))
        if not self.scheduler.running:
            return False

        if spec_k:
            if self._spec_ready(spec_k):
                self._spec_step(clock, spec_k)
                return True
            # a slot too close to capacity / an unmapped page under pool
            # pressure: advance everyone one plain token this step
            self.spec_fallbacks += 1

        with span("engine.decode.stage",
                  rows=len(self.scheduler.running)) as stage:
            tokens = self._last_tok[:, None]  # (B, 1[, K])
            pos = self._put(self._slot_len, jnp.int32)
            batch = {"tokens": self._put(tokens)}
            if self._paged:
                batch["block_tables"] = self._put(self._block_tables)
            samp = {k: self._put(v) for k, v in self._samp.items()}
        with span("engine.decode.launch"), self._ctx():
            toks_dev, self.caches = self._decode_fn(
                self.params, self.caches, batch, pos, samp)
        # a successful decode proves the engine itself is healthy, so
        # keep isolating whatever admissions are failing — the trip is
        # for a broken engine, not a kill switch one bad client can pull
        # while co-batched traffic is being served fine
        self._admit_fail_streak = 0
        # token ids only — logits stay on device (np.asarray of a jax
        # array is a read-only view; copy so _last_tok stays writable)
        with span("engine.decode.wait"):
            toks = np.array(toks_dev)
        with span("engine.emit") as emit:
            self.decode_steps += 1
            self._slot_len += 1  # every row's in-graph cursor advanced by 1
            self._samp["step"] += 1
            self._last_tok = toks
            live = list(self.scheduler.running.items())
            self.decode_rows += len(live)
            for slot, rs in live:
                t = toks[slot]
                rs.generated.append(t.tolist() if t.ndim else int(t))
                if self.token_sink is not None:
                    self.token_sink(rs.request.rid, rs.generated[-1])
                self._maybe_finish(rs, clock)
        if obs is not None:
            obs.decode_step(stage.t0, emit.t1, emitted=len(live),
                            gauges=self._obs_gauges())
        return True

    def drain_finished(self) -> List[RequestState]:
        """Hand over (and forget) finished request states, and clear the
        metrics archive with them. Long-lived ``submit()``/``step()``
        callers must drain periodically or the retained token lists grow
        without bound. Safe at any point: ``run()`` accounts its own
        completions in a run-local sink, not by slicing ``completed``."""
        out, self.finished = self.finished, []
        self.completed = []
        self.aborted = []
        return out

    def run(self, requests: Sequence[Request] = ()) -> Dict[str, float]:
        """Drive the request set to completion; returns aggregate metrics
        for the requests completed by *this* call (its own clock)."""
        for r in requests:
            self.submit(r)
        self._run_sink = sink = []
        self._t0 = time.monotonic()
        try:
            while self.queue or self.scheduler.running:
                if not self.step():
                    nxt = self.queue.next_arrival()
                    if nxt is not None:
                        time.sleep(min(max(nxt - self._now(), 0.0), 0.05))
        finally:
            self._run_sink = None
        return summarize(sink, self._now())
