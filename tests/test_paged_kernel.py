"""Paged-attend Pallas kernel vs the jnp reference backend (interpret
mode — the CPU CI leg runs these with REPRO_KERNEL_INTERPRET=1)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.lns import LNSFormat, compute_scale, lns_encode, lns_pack
from repro.kernels.dispatch import (_fused_sample_reference,
                                    _paged_attend_reference, fused_sample)
from repro.kernels.ops import paged_attend_blocktable, paged_attend_decode

pytestmark = pytest.mark.interpret


def _setup(seed=0, B=3, h=6, kv=2, hd=16, page=4, mp=5, P=11, S=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, h, hd)), jnp.float32)
    kd = rng.normal(size=(P + 1, kv, page, hd)).astype(np.float32)
    vd = rng.normal(size=(P + 1, kv, page, hd)).astype(np.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
    lengths = jnp.asarray(rng.integers(max(S, 1), mp * page + 1, (B,)),
                          jnp.int32)
    return q, kd, vd, tbl, lengths


def _assert_parity(q, kd, vd, tbl, lengths, **kw):
    ref = _paged_attend_reference(q, jnp.asarray(kd), jnp.asarray(vd),
                                  None, None, tbl, lengths,
                                  fmt=None, softcap=None, sm_scale=0.25, **kw)
    ker = paged_attend_blocktable(q, jnp.asarray(kd), jnp.asarray(vd),
                                  None, None, tbl, lengths,
                                  fmt=None, softcap=None, sm_scale=0.25,
                                  interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)
    return ker


def test_kernel_matches_reference_dense_pool():
    q, kd, vd, tbl, lengths = _setup()
    ref = _paged_attend_reference(q, jnp.asarray(kd), jnp.asarray(vd),
                                  None, None, tbl, lengths,
                                  fmt=None, softcap=None, sm_scale=0.25)
    ker = paged_attend_decode(q, jnp.asarray(kd), jnp.asarray(vd),
                              None, None, tbl, lengths,
                              fmt=None, softcap=None, sm_scale=0.25,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)


def test_kernel_matches_reference_lns_pool_with_softcap():
    """Packed-LNS pages decode tile-locally inside the kernel (the shared
    core.lns decode), scales applied per position/head."""
    q, kd, vd, tbl, lengths = _setup(seed=1)
    fmt = LNSFormat(bits=8, gamma=8)

    def enc(x):
        s = compute_scale(jnp.asarray(x), axis=(0, 1, 2))
        sign, code = lns_encode(jnp.asarray(x), fmt, s)
        scale = jnp.broadcast_to(s, x.shape[:-1] + (1,)).astype(jnp.bfloat16)
        return lns_pack(sign, code, fmt), scale

    pk, sk = enc(kd)
    pv, sv = enc(vd)
    ref = _paged_attend_reference(q, pk, pv, sk, sv, tbl, lengths,
                                  fmt=fmt, softcap=30.0, sm_scale=0.25)
    ker = paged_attend_decode(q, pk, pv, sk, sv, tbl, lengths,
                              fmt=fmt, softcap=30.0, sm_scale=0.25,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)


def test_kernel_single_valid_position():
    """length == 1 (a slot right after a 1-token prompt): the online
    softmax must not divide by a zero denominator on later pages."""
    q, kd, vd, tbl, _ = _setup(seed=2)
    lengths = jnp.asarray([1, 1, 1], jnp.int32)
    ref = _paged_attend_reference(q, jnp.asarray(kd), jnp.asarray(vd),
                                  None, None, tbl, lengths,
                                  fmt=None, softcap=None, sm_scale=0.25)
    ker = paged_attend_decode(q, jnp.asarray(kd), jnp.asarray(vd),
                              None, None, tbl, lengths,
                              fmt=None, softcap=None, sm_scale=0.25,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(ker)).all()


def test_kernel_partial_last_page():
    """Lengths that end mid-page: the tail positions of the final gathered
    page must be masked out, not averaged in."""
    q, kd, vd, tbl, _ = _setup(seed=3)
    page, mp = 4, 5
    # one length per interesting phase: 1 into a page, page-1, exactly full
    lengths = jnp.asarray([page + 1, 2 * page - 1, mp * page], jnp.int32)
    _assert_parity(q, kd, vd, tbl, lengths)


def test_kernel_null_page_entries():
    """Unreserved tail entries of a block table point at the sacrificial
    null page (pool index P). They sit beyond ``lengths`` so the causal
    mask must hide whatever garbage the null page holds."""
    q, kd, vd, tbl, _ = _setup(seed=4, P=11)
    np.asarray(kd)[11] = 1e30  # poison the null page
    np.asarray(vd)[11] = -1e30
    tbl = np.asarray(tbl).copy()
    lengths = jnp.asarray([5, 9, 2], jnp.int32)  # 2, 3, 1 pages reserved
    for b, n in enumerate([2, 3, 1]):
        tbl[b, n:] = 11  # null out everything past the reservation
    ker = _assert_parity(q, kd, vd, jnp.asarray(tbl), lengths)
    assert np.isfinite(np.asarray(ker)).all()


def test_kernel_prefix_shared_tables():
    """Two slots whose tables alias the same physical prefix pages (the
    prefix cache's CoW sharing) must each read the shared pages correctly;
    parity additionally pins the aliased reads to the gather oracle."""
    q, kd, vd, _, _ = _setup(seed=5, B=2, mp=4, P=9)
    tbl = jnp.asarray([[3, 7, 2, 5],
                       [3, 7, 8, 6]], jnp.int32)  # pages 3,7 shared
    lengths = jnp.asarray([14, 11], jnp.int32)
    ker = _assert_parity(q, kd, vd, tbl, lengths)
    # the shared prefix really is the same memory: re-run slot 1 with
    # slot 0's suffix pages — positions inside the shared prefix agree
    q0 = q[:1]
    ref_a = _paged_attend_reference(
        q0, jnp.asarray(kd), jnp.asarray(vd), None, None, tbl[:1],
        jnp.asarray([8], jnp.int32), fmt=None, softcap=None, sm_scale=0.25)
    ref_b = _paged_attend_reference(
        q0, jnp.asarray(kd), jnp.asarray(vd), None, None, tbl[1:],
        jnp.asarray([8], jnp.int32), fmt=None, softcap=None, sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(ref_a), np.asarray(ref_b),
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(ker)).all()


@pytest.mark.parametrize("page,mp", [(4, 5), (8, 3), (16, 2)])
def test_kernel_page_size_parity(page, mp):
    """The same sequence budget under different page sizes: the kernel's
    per-page loop must be parametric in the block size."""
    q, kd, vd, tbl, lengths = _setup(seed=6, page=page, mp=mp)
    _assert_parity(q, kd, vd, tbl, lengths)


def test_kernel_prefill_over_block_table():
    """S > 1 (the engine's suffix prefill over a prefix-cached table):
    causal masking applies per query row, not just at the tail."""
    q, kd, vd, tbl, _ = _setup(seed=7, S=6)
    lengths = jnp.asarray([6, 13, 20], jnp.int32)  # n_cached = 0, 7, 14
    _assert_parity(q, kd, vd, tbl, lengths)


def test_kernel_prefill_lns_pool_softcap():
    q, kd, vd, tbl, _ = _setup(seed=8, S=4)
    lengths = jnp.asarray([4, 11, 17], jnp.int32)
    fmt = LNSFormat(bits=8, gamma=8)

    def enc(x):
        s = compute_scale(jnp.asarray(x), axis=(0, 1, 2))
        sign, code = lns_encode(jnp.asarray(x), fmt, s)
        scale = jnp.broadcast_to(s, x.shape[:-1] + (1,)).astype(jnp.bfloat16)
        return lns_pack(sign, code, fmt), scale

    pk, sk = enc(kd)
    pv, sv = enc(vd)
    ref = _paged_attend_reference(q, pk, pv, sk, sv, tbl, lengths,
                                  fmt=fmt, softcap=30.0, sm_scale=0.25)
    ker = paged_attend_blocktable(q, pk, pv, sk, sv, tbl, lengths,
                                  fmt=fmt, softcap=30.0, sm_scale=0.25,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused sampler epilogue


def test_fused_sample_greedy_bit_exact():
    """Greedy rows (gumbel=None) are bit-exact between backends including
    first-max-wins tie-breaking on duplicated maxima."""
    rng = np.random.default_rng(9)
    lg = rng.normal(size=(6, 300)).astype(np.float32)
    lg[2, 5] = lg[2, 77] = 50.0  # duplicated max: must pick index 5
    lg = jnp.asarray(lg)
    ref = _fused_sample_reference(lg, None, None)
    ker = fused_sample(lg, None, None, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert int(np.asarray(ker)[2]) == 5


def test_fused_sample_mixed_temperature_bit_exact():
    """Per-row temps (0 and >0 mixed in one batch): gumbel sampling where
    temp>0, greedy where temp==0 — same tokens on both backends, so a
    seeded request replays identically whichever backend serves it."""
    rng = np.random.default_rng(10)
    B, V = 8, 130  # V=130: exercises the pad-to-128-multiple path
    lg = jnp.asarray(rng.normal(size=(B, V)), jnp.float32)
    gum = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
    temp = jnp.asarray([0.0, 0.7, 1.0, 0.0, 1.3, 0.2, 0.0, 2.0], jnp.float32)
    ref = _fused_sample_reference(lg, gum, temp)
    ker = fused_sample(lg, gum, temp, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    # the temp==0 rows really are the greedy tokens
    greedy = np.argmax(np.asarray(lg), axis=-1)
    for b in (0, 3, 6):
        assert int(np.asarray(ker)[b]) == int(greedy[b])


def test_engine_decode_routes_through_kernel(monkeypatch):
    """Pallas backend + interpret: the paged engine's decode path reaches
    the kernel and still matches the reference backend."""
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    from repro.configs import get_smoke_config
    from repro.kernels import dispatch
    from repro.core.quantizer import QuantConfig
    from repro.optim.madam import MadamConfig
    from repro.serving import Engine, Request
    from repro.training import init_train_state

    cfg = get_smoke_config("smollm-135m")
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(update_format=LNSFormat(bits=8, gamma=8))
    params = init_train_state(jax.random.PRNGKey(0), cfg, mcfg).params

    def mk():
        rng = np.random.default_rng(4)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, (6,)).tolist(),
                        max_new_tokens=4) for i in range(2)]

    ref_eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=16,
                     page_size=4)
    ref_eng.run(mk())
    ref = {rs.request.rid: rs.generated for rs in ref_eng.finished}

    with dispatch.configured(backend="pallas"):
        kern_eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=16,
                          page_size=4)
        kern_eng.run(mk())
    got = {rs.request.rid: rs.generated for rs in kern_eng.finished}
    assert ref == got
