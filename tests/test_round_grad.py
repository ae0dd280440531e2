"""Tests for the fused round-gradient path (`repro.kernels.round_grad`,
the dispatchers in `repro.core.aggregation` and every strategy's round).

Four layers of guarantees:

  * kernel parity — each Pallas variant (masked, coded single-launch,
    tier-masked) matches its pure-jnp oracle in interpret mode across
    ragged/odd shapes, forced-small tiles, zero-weight rows, the
    `w=None` and `c == 0` degenerate cases, and a scalar parity weight;
  * packing — `packed_row_indices` bucket-pads the systematic support
    and the padding rows carry weight zero (exact-zero contributions);
  * session parity — every strategy's `Session` trace matches the
    float64 NumPy oracle (`_oracle.run`, over the same plan and the same
    drawn schedule) to rtol 1e-3 / atol 1e-6 with bit-identical
    durations, flat and tiered, under both operand layouts;
  * dispatcher stability — the dispatchers' jnp expressions are exactly
    the two-pass sums (`array_equal` against hand-written jnp).
"""
import _oracle as oracle
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Session, TrainData, make_strategy
from repro.core import aggregation, cfl
from repro.fleet import FleetTopology
from repro.kernels.round_grad import ops as rg_ops
from repro.kernels.round_grad import ref as rg_ref
from repro.sim.network import paper_fleet, wireless_fleet

EPOCHS = 10
LR = 0.05
N, ELL, D = 12, 60, 40


def _rand(shape, seed, positive=False):
    key = jax.random.PRNGKey(seed)
    if positive:
        return jax.random.uniform(key, shape)
    return jax.random.normal(key, shape)


# ---------------------------------------------------------------------------
# kernel parity vs the jnp oracles (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,d,bm", [(1, 1, 8), (7, 5, 3), (64, 16, 64),
                                    (130, 33, 32), (300, 41, 128)])
def test_masked_matches_ref(m, d, bm):
    x = _rand((m, d), m + d)
    y = _rand((m,), m + d + 1)
    w = _rand((m,), m + d + 2, positive=True)
    beta = _rand((d,), m + d + 3)
    got = rg_ops.masked_round_gradient(x, y, w, beta, block_m=bm,
                                       force_interpret=True)
    want = rg_ref.masked_round_gradient(x, y, w, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_masked_default_weights_are_ones():
    x, y, beta = _rand((33, 7), 0), _rand((33,), 1), _rand((7,), 2)
    got = rg_ops.masked_round_gradient(x, y, None, beta, block_m=16,
                                       force_interpret=True)
    want = rg_ref.masked_round_gradient(x, y, jnp.ones_like(y), beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_masked_zero_weight_rows_drop_out():
    """Rows at weight 0 contribute exactly nothing — the packed layout's
    validity-mask contract."""
    x, y, beta = _rand((40, 6), 3), _rand((40,), 4), _rand((6,), 5)
    w = np.ones(40, dtype=np.float32)
    w[13:] = 0.0
    got = rg_ops.masked_round_gradient(x, y, jnp.asarray(w), beta,
                                       block_m=16, force_interpret=True)
    want = rg_ops.masked_round_gradient(x[:13], y[:13], jnp.ones(13),
                                        beta, block_m=16,
                                        force_interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ms,mp,d,bm", [(37, 11, 8, 16), (64, 64, 16, 32),
                                        (5, 129, 12, 64)])
def test_coded_single_launch_matches_ref(ms, mp, d, bm):
    x = _rand((ms, d), 10)
    y = _rand((ms,), 11)
    w = _rand((ms,), 12, positive=True)
    xp = _rand((mp, d), 13)
    yp = _rand((mp,), 14)
    wp = _rand((mp,), 15, positive=True)
    beta = _rand((d,), 16)
    got = rg_ops.coded_round_gradient(x, y, w, xp, yp, wp, beta,
                                      block_m=bm, force_interpret=True)
    want = rg_ref.coded_round_gradient(x, y, w, xp, yp, wp, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_coded_scalar_parity_weight_broadcasts():
    x, y = _rand((20, 5), 20), _rand((20,), 21)
    w = _rand((20,), 22, positive=True)
    xp, yp = _rand((9, 5), 23), _rand((9,), 24)
    beta = _rand((5,), 25)
    got = rg_ops.coded_round_gradient(x, y, w, xp, yp,
                                      jnp.asarray(0.25), beta,
                                      block_m=8, force_interpret=True)
    want = rg_ref.coded_round_gradient(x, y, w, xp, yp,
                                       jnp.full((9,), 0.25), beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_coded_empty_parity_falls_back_to_masked():
    """c == 0: the parity block is (0, d) and cannot be block-fetched;
    the ops wrapper must route to the masked variant."""
    x, y = _rand((24, 6), 30), _rand((24,), 31)
    w = _rand((24,), 32, positive=True)
    beta = _rand((6,), 33)
    got = rg_ops.coded_round_gradient(
        x, y, w, jnp.zeros((0, 6)), jnp.zeros((0,)), jnp.asarray(1.0),
        beta, block_m=8, force_interpret=True)
    want = rg_ops.masked_round_gradient(x, y, w, beta, block_m=8,
                                        force_interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,d,t,bm", [(50, 9, 1, 16), (64, 8, 3, 32),
                                      (77, 12, 4, 16)])
def test_tier_masked_matches_ref_and_tier_reduce(m, d, t, bm):
    x = _rand((m, d), 40)
    y = _rand((m,), 41)
    w = _rand((m,), 42, positive=True)
    beta = _rand((d,), 43)
    masks = (jax.random.uniform(jax.random.PRNGKey(44), (t, m)) < 0.5
             ).astype(x.dtype)
    got = rg_ops.tier_masked_round_gradient(x, y, w, masks, beta,
                                            block_m=bm,
                                            force_interpret=True)
    want = rg_ref.tier_masked_round_gradient(x, y, w, masks, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the oracle itself is the tier_reduce contraction the tiered
    # reference path uses
    contrib = (x @ beta - y) * w
    via_reduce = aggregation.tier_reduce(contrib, x, masks)
    np.testing.assert_allclose(np.asarray(want), np.asarray(via_reduce),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,d", [(1100, 200), (700, 3000)])
def test_variants_at_chip_default_tile(m, d):
    """The TPU's default row tile with a ragged m (zero-weight padding
    rows) and a d off the 128-lane multiple; at d = 3000 the VMEM cap
    shrinks the tile (to 512 rows flat, 256 for the two coded streams)."""
    from repro.kernels.round_grad import round_grad as rg_k
    bm = rg_k.DEFAULT_BLOCK_M
    x, y = _rand((m, d), 80), _rand((m,), 81)
    w, beta = _rand((m,), 82, positive=True), _rand((d,), 83)
    xp, yp = _rand((m // 3, d), 84), _rand((m // 3,), 85)
    wp = _rand((m // 3,), 86, positive=True)
    masks = (jax.random.uniform(jax.random.PRNGKey(87), (3, m)) < 0.5
             ).astype(x.dtype)
    pairs = [
        (rg_ops.masked_round_gradient(x, y, w, beta, block_m=bm,
                                      force_interpret=True),
         rg_ref.masked_round_gradient(x, y, w, beta)),
        (rg_ops.coded_round_gradient(x, y, w, xp, yp, wp, beta, block_m=bm,
                                     force_interpret=True),
         rg_ref.coded_round_gradient(x, y, w, xp, yp, wp, beta)),
        (rg_ops.tier_masked_round_gradient(x, y, w, masks, beta,
                                           block_m=bm, force_interpret=True),
         rg_ref.tier_masked_round_gradient(x, y, w, masks, beta)),
    ]
    for got, want in pairs:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)
    assert rg_k._row_tile(bm, m, d, streams=1) == (bm if d < 1024 else 512)
    assert rg_k._row_tile(bm, m, d, streams=2) == (bm if d < 1024 else 256)


def test_tier_single_tier_row_is_flat_masked():
    """A ones mask with T == 1 reproduces the flat masked launch bitwise
    (same tile, same accumulation order) — the single-tier-bit-exact
    contract the hierarchy layer relies on."""
    x, y = _rand((48, 10), 50), _rand((48,), 51)
    w = _rand((48,), 52, positive=True)
    beta = _rand((10,), 53)
    tier = rg_ops.tier_masked_round_gradient(
        x, y, w, jnp.ones((1, 48)), beta, block_m=16,
        force_interpret=True)
    flat = rg_ops.masked_round_gradient(x, y, w, beta, block_m=16,
                                        force_interpret=True)
    np.testing.assert_array_equal(np.asarray(tier[0]), np.asarray(flat))


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------

def test_packed_row_indices_bucket_pads():
    load = np.zeros(2000, dtype=np.float32)
    support = np.arange(0, 1400)
    load[support] = 1.0
    idx, valid = cfl.packed_row_indices(load)
    assert idx.shape == (3 * cfl.PACK_BLOCK,)  # 1400 -> 1536
    np.testing.assert_array_equal(idx[:1400], support)
    np.testing.assert_array_equal(valid[:1400], True)
    np.testing.assert_array_equal(valid[1400:], False)
    np.testing.assert_array_equal(idx[1400:], 0)  # padding stays in-range


def test_packed_row_indices_empty_support():
    idx, valid = cfl.packed_row_indices(np.zeros(100))
    assert idx.shape == (cfl.PACK_MIN,)
    assert not valid.any()


def test_fused_device_state_is_memoized():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=N, d=D)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=N, ell=ELL, d=D)
    strat = make_strategy("cfl", key_seed=7, fixed_c=int(0.3 * data.m))
    state = strat.plan(fleet, data)
    dev1 = cfl.fused_coded_device_state(state, data)
    dev2 = cfl.fused_coded_device_state(state, data)
    assert dev1 is dev2
    assert cfl.fused_coded_device_state(state, data, parity_rows=True) \
        is not dev1


def test_fused_device_state_dense_fallback(monkeypatch):
    """Near-full supports skip packing: the dict reuses the shared
    data_device_keys names (x/y/row_client — replicated, not stacked,
    across sweep lanes) with the load mask as the base row weight, so
    every dense lane of a nu-ladder sweep shares one engine bucket."""
    fleet = paper_fleet(0.2, 0.2, seed=1, n=N, d=D)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=N, ell=ELL, d=D)
    strat = make_strategy("cfl", key_seed=7, fixed_c=int(0.3 * data.m))
    state = strat.plan(fleet, data)

    monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", 0.0)
    state._fused_dev = None
    dense = cfl.fused_coded_device_state(state, data)
    assert {"x", "y", "row_client", "sys_w"} <= set(dense)
    assert "sys_x" not in dense
    assert dense["x"].shape == (data.m, data.d)
    np.testing.assert_array_equal(
        np.asarray(dense["sys_w"]),
        np.asarray(state.load_mask).reshape(data.m))

    monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", float("inf"))
    state._fused_dev = None
    packed = cfl.fused_coded_device_state(state, data)
    assert "sys_x" in packed and "x" not in packed


# ---------------------------------------------------------------------------
# session parity: Session vs the float64 oracle, all strategies
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    fleet = paper_fleet(0.2, 0.2, seed=1, n=N, d=D)
    wfleet = wireless_fleet(0.2, 0.2, nu_erasure=0.3, seed=0, n=N, d=D)
    data = TrainData.linreg(jax.random.PRNGKey(0), n=N, ell=ELL, d=D)
    return fleet, wfleet, data


CASES = ["uncoded", "cfl", "cfl_c0", "gradcode", "scfl", "scfl_rho",
         "lowlat", "cfedl_rff", "cfedl_id"]


def _case(name: str, data):
    c = int(0.3 * data.m)
    if name == "uncoded":
        return make_strategy("uncoded"), "paper"
    if name == "cfl":
        return make_strategy("cfl", key_seed=7, fixed_c=c), "paper"
    if name == "cfl_c0":
        return make_strategy("cfl", key_seed=7, fixed_c=0), "paper"
    if name == "gradcode":
        return make_strategy("gradcode", r=3), "paper"
    if name == "scfl":
        return make_strategy("stochastic", key_seed=7, fixed_c=c,
                             noise_multiplier=0.5, rounds=EPOCHS), \
            "wireless"
    if name == "scfl_rho":
        return make_strategy("stochastic", key_seed=7, fixed_c=c,
                             noise_multiplier=0.5, sample_frac=0.8,
                             rounds=EPOCHS), "wireless"
    if name == "lowlat":
        return make_strategy("lowlatency", key_seed=7, fixed_c=c,
                             chunks=4), "wireless"
    if name == "cfedl_rff":
        # d_feat == data.d so nmse-vs-beta_true stays well defined
        return make_strategy("codedfedl", key_seed=7, fixed_c=c,
                             d_feat=D, rff_gamma=0.05), "paper"
    if name == "cfedl_id":
        return make_strategy("codedfedl", key_seed=7, fixed_c=c), "paper"
    raise ValueError(name)


def _assert_matches_oracle(strategy, flt, data, seed=3):
    """One planned state, one schedule drawn from `seed`: the Session's
    trace against the oracle's replay of the same epochs."""
    state = strategy.plan(flt, data)
    rep = Session(strategy=strategy, fleet=flt, lr=LR, epochs=EPOCHS,
                  seed=seed).run(data, rng=np.random.default_rng(seed),
                                 state=state)
    sched = strategy.sample_epochs(state, flt, EPOCHS,
                                   np.random.default_rng(seed))
    nmse, times, beta = oracle.run(strategy, state, data, sched, LR)
    np.testing.assert_array_equal(rep.epoch_durations, sched.durations)
    np.testing.assert_array_equal(rep.times, times)
    np.testing.assert_allclose(rep.nmse, nmse, rtol=1e-3, atol=1e-6)
    # the NMSE of a slow learner (cfedl_rff) barely moves in EPOCHS
    # epochs; the iterate itself pins every gradient
    np.testing.assert_allclose(rep.beta, beta, rtol=1e-3, atol=1e-6)


# The small-fleet plans load nearly every row, so the natural layout is
# the dense fallback; pinning PACK_DENSE_FRAC exercises the packed
# layout through the same engines.
LAYOUTS = {"packed": float("inf"), "dense": 0.0}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name", CASES)
def test_fused_matches_reference_flat(name, layout, small, monkeypatch):
    monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", LAYOUTS[layout])
    fleet, wfleet, data = small
    strat, which = _case(name, data)
    flt = fleet if which == "paper" else wfleet
    _assert_matches_oracle(strat, flt, data)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name", ["cfl", "scfl", "scfl_rho", "lowlat",
                                  "cfedl_rff", "cfedl_id"])
def test_fused_matches_reference_tiered(name, layout, small, monkeypatch):
    monkeypatch.setattr(cfl, "PACK_DENSE_FRAC", LAYOUTS[layout])
    fleet, wfleet, data = small
    strat, which = _case(name, data)
    flt = fleet if which == "paper" else wfleet
    topo = FleetTopology.uniform(N, 3)
    _assert_matches_oracle(
        make_strategy("hierarchical", base=strat, topology=topo),
        flt, data)


# ---------------------------------------------------------------------------
# dispatcher stability: the jnp expressions ARE the two-pass sums
# ---------------------------------------------------------------------------

def test_reference_round_gradient_is_pre_fusion_expression():
    x, y = _rand((50, 8), 60), _rand((50,), 61)
    w = _rand((50,), 62, positive=True)
    beta = _rand((8,), 63)
    resid = x @ beta - y
    np.testing.assert_array_equal(
        np.asarray(aggregation.round_gradient(x, y, beta)),
        np.asarray(resid @ x))
    np.testing.assert_array_equal(
        np.asarray(aggregation.round_gradient(x, y, beta, w=w)),
        np.asarray((resid * w) @ x))


def test_reference_coded_gradient_is_pre_fusion_expression():
    x, y = _rand((40, 6), 70), _rand((40,), 71)
    w = _rand((40,), 72, positive=True)
    xp, yp = _rand((15, 6), 73), _rand((15,), 74)
    wp = jnp.full((15,), 1.0 / 15)
    beta = _rand((6,), 75)
    want = ((x @ beta - y) * w) @ x + ((xp @ beta - yp) * wp) @ xp
    got = aggregation.coded_round_gradient(x, y, w, xp, yp, wp, beta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gram_parity_gradient_matches_two_pass():
    """The Gram-folded Eq. 18 term equals the two-pass parity gradient
    up to float reassociation."""
    xp, yp = _rand((30, 7), 80), _rand((30,), 81)
    beta = _rand((7,), 82)
    gram, gramy = aggregation.parity_gram(xp, yp)
    got = aggregation.gram_parity_gradient(gram, gramy, beta,
                                           jnp.asarray(30.0))
    want = ((xp @ beta - yp) / 30.0) @ xp
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
