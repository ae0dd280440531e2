"""Autotuner tests: cache round-trip/versioning, roofline pruning,
deterministic winner selection, `block="auto"` bit-parity across every
kernel entry point, and the perf-trend trajectory gate.

Kernel-touching tests use tiny shapes whose buckets do NOT collide with
the committed `src/repro/tune/defaults.json` entries, and the user cache
is redirected to a tmpdir via $REPRO_TUNE_CACHE_DIR — so `block="auto"`
cold-miss behaviour is actually exercised.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.kernels import common as kcommon
from repro.kernels.coded_grad import coded_grad as _cg
from repro.kernels.coded_grad import ops as cg_ops
from repro.kernels.encode import encode as _en
from repro.kernels.encode import ops as en_ops
from repro.tune import cache as tc
from repro.tune import tuner

# the benchmarks package lives at the repo root, outside src/
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
from benchmarks import perf_trend  # noqa: E402


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """User tile cache redirected to a fresh tmpdir (initially empty)."""
    monkeypatch.setenv(tc.CACHE_ENV, str(tmp_path))
    return tc.TileCache(tc.user_cache_path())


# ---------------------------------------------------------------------------
# cache: keys, round-trip, versioning, fallback order
# ---------------------------------------------------------------------------

def test_bucket_shape_pow2_ceiling():
    assert tc.bucket_shape((936, 300, 500)) == (1024, 512, 512)
    assert tc.bucket_shape((1024,)) == (1024,)
    assert tc.bucket_shape((1, 3)) == (1, 4)


def test_cache_key_separates_family_backend_bucket():
    k1 = tc.cache_key("encode", (936, 300, 500), "cpu")
    assert k1 == "encode|cpu|1024x512x512"
    assert tc.cache_key("encode", (1000, 400, 510), "cpu") == k1  # same bucket
    assert tc.cache_key("encode", (936, 300, 500), "tpu") != k1
    assert tc.cache_key("encode_prng", (936, 300, 500), "cpu") != k1


def test_cache_round_trip(tmp_cache):
    tmp_cache.store("encode", (64, 48, 32), "cpu", (32, 32, 32),
                    {"us": 12.5})
    ent = tmp_cache.lookup("encode", (64, 48, 32), "cpu")
    assert ent["block"] == [32, 32, 32] and ent["us"] == 12.5
    # same bucket, different concrete shape -> same entry
    assert tmp_cache.lookup("encode", (50, 40, 30), "cpu") == ent
    assert tc.lookup_block("encode", (64, 48, 32), "cpu") == (32, 32, 32)


def test_cache_store_merges(tmp_cache):
    tmp_cache.store("encode", (64, 48, 32), "cpu", (32, 32, 32))
    tmp_cache.store("coded_grad", (96, 12), "cpu", (64,))
    assert tc.lookup_block("encode", (64, 48, 32), "cpu") == (32, 32, 32)
    assert tc.lookup_block("coded_grad", (96, 12), "cpu") == (64,)


def test_cache_version_mismatch_invalidates(tmp_cache):
    key = tc.cache_key("encode", (64, 48, 32), "cpu")
    os.makedirs(os.path.dirname(tmp_cache.path), exist_ok=True)
    with open(tmp_cache.path, "w") as f:
        json.dump({"version": tc.CACHE_VERSION + 1,
                   "entries": {key: {"block": [8, 8, 8]}}}, f)
    # stale-version file reads as empty ...
    assert tc.lookup_block("encode", (64, 48, 32), "cpu") is None
    # ... and the first store drops its entries wholesale
    tmp_cache.store("coded_grad", (96, 12), "cpu", (64,))
    with open(tmp_cache.path) as f:
        payload = json.load(f)
    assert payload["version"] == tc.CACHE_VERSION
    assert key not in payload["entries"]


def test_committed_defaults_cover_ci_shapes():
    """The in-repo defaults.json must hit for every CPU CI shape — this
    is what makes `block="auto"` tuned on fresh checkouts/CI runners."""
    from repro.tune.families import CI_SHAPES

    for family, shapes in CI_SHAPES.items():
        for shape in shapes:
            ent = tc._load_entries(tc.defaults_path()).get(
                tc.cache_key(family, shape, "cpu"))
            assert ent is not None, (family, shape)
            want_len = 1 if family in ("coded_grad", "round_grad") else 3
            assert len(ent["block"]) == want_len, (family, shape)


def test_user_cache_wins_over_defaults(tmp_cache):
    # (936, 300, 500) IS in the committed defaults; a user entry shadows it
    repo_block = tc.lookup_block("encode", (936, 300, 500), "cpu")
    assert repo_block is not None
    tmp_cache.store("encode", (936, 300, 500), "cpu", (128, 128, 128))
    assert tc.lookup_block("encode", (936, 300, 500), "cpu") == \
        (128, 128, 128)


def test_unset_cache_dir_reads_only_committed_defaults(tmp_path,
                                                       monkeypatch):
    """With $REPRO_TUNE_CACHE_DIR unset no file outside the checkout is
    read: a tiles.json under the home directory is ignored."""
    monkeypatch.delenv(tc.CACHE_ENV, raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    stray = tc.TileCache(str(tmp_path / ".cache" / "repro-tune" /
                             tc.CACHE_FILENAME))
    stray.store("encode", (936, 300, 500), "cpu", (8, 8, 8))
    stray.store("encode", (64, 48, 32), "cpu", (8, 8, 8))
    assert tc.user_cache_path() is None
    repo = tc._load_entries(tc.defaults_path())
    assert tc.lookup_block("encode", (936, 300, 500), "cpu") == tuple(
        repo[tc.cache_key("encode", (936, 300, 500), "cpu")]["block"])
    assert tc.lookup_block("encode", (64, 48, 32), "cpu") is None
    with pytest.raises(ValueError, match=tc.CACHE_ENV):
        tuner.autotune("encode", (64, 48, 32),
                       terms_fn=lambda b: {"t_compute": 1.0, "t_memory": 1.0},
                       measure_fn=lambda b: 1.0)


# ---------------------------------------------------------------------------
# tuner: pruning + deterministic winner (stubbed terms/measure)
# ---------------------------------------------------------------------------

def test_prune_keeps_within_slack_of_best():
    cands = [(256,), (512,), (1024,), (2048,)]
    bounds = [10.0, 19.9, 20.1, 100.0]
    survivors, pruned = tuner.prune(cands, bounds, slack=2.0)
    assert survivors == [(256,), (512,)]
    assert pruned == [(1024,), (2048,)]
    assert sorted(survivors + pruned) == sorted(cands)


def test_prune_zero_bound_keeps_everything():
    """A degenerate lowering with a 0 roofline bound must not collapse
    the slack band and prune every positive-bound candidate."""
    cands = [(256,), (512,), (1024,)]
    survivors, pruned = tuner.prune(cands, [0.0, 5.0, 9.0], slack=2.0)
    assert survivors == cands
    assert pruned == []


def test_roofline_bound_is_binding_term():
    assert tuner.roofline_bound({"t_compute": 2.0, "t_memory": 5.0}) == 5.0
    assert tuner.roofline_bound({"t_compute": 7.0, "t_memory": 5.0}) == 7.0


def test_autotune_measures_only_survivors():
    """A candidate dominated under the roofline model is pruned without
    ever being executed."""
    measured = []

    def terms_fn(block):
        # (512,) gets a 10x-worse lower bound -> pruned at slack=2
        bad = block == (512,)
        return {"t_compute": 10.0 if bad else 1.0, "t_memory": 0.0}

    def measure_fn(block):
        measured.append(block)
        return 100.0

    res = tuner.autotune("coded_grad", (512, 16), slack=2.0,
                         backend="cpu", store=False,
                         terms_fn=terms_fn, measure_fn=measure_fn)
    assert (512,) in res.pruned
    assert (512,) not in measured
    assert measured  # survivors were measured
    # every pruned candidate is provably dominated under the model
    best = min(res.bounds_us)
    for cand, bound in zip(res.candidates, res.bounds_us):
        assert (cand in res.pruned) == (bound > 2.0 * best)


def test_autotune_winner_deterministic_with_ties():
    """Equal measurements -> the EARLIEST candidate in enumeration order
    wins, and a rerun reproduces it exactly."""
    def terms_fn(block):
        return {"t_compute": 1.0, "t_memory": 1.0}

    def measure_fn(block):
        return 42.0  # all tied

    first = tuner.autotune("coded_grad", (512, 16), backend="cpu",
                           store=False, terms_fn=terms_fn,
                           measure_fn=measure_fn)
    again = tuner.autotune("coded_grad", (512, 16), backend="cpu",
                           store=False, terms_fn=terms_fn,
                           measure_fn=measure_fn)
    assert first.block == again.block == first.candidates[0]


def test_autotune_picks_fastest_and_persists(tmp_cache):
    def terms_fn(block):
        return {"t_compute": 1.0, "t_memory": 1.0}

    def measure_fn(block):
        return 10.0 if block == (512,) else 50.0

    res = tuner.autotune("coded_grad", (512, 16), backend="cpu",
                         cache=tmp_cache, terms_fn=terms_fn,
                         measure_fn=measure_fn)
    assert res.block == (512,)
    assert tc.lookup_block("coded_grad", (512, 16), "cpu") == (512,)


def test_candidate_terms_block_sensitive():
    """Real dry-run lowerings: smaller tiles re-stream resident operands
    once per grid step, so the roofline memory term must grow as tiles
    shrink (this is the signal pruning relies on)."""
    from repro.tune.families import FAMILIES

    fam = FAMILIES["coded_grad"]
    shape = (1024, 64)
    b_small = tuner.roofline_bound(
        tuner.candidate_terms(fam, shape, (256,)))
    b_whole = tuner.roofline_bound(
        tuner.candidate_terms(fam, shape, (1024,)))
    assert b_small > b_whole


# ---------------------------------------------------------------------------
# block="auto" bit-parity across every kernel entry point
# ---------------------------------------------------------------------------

def _encode_args(c=64, ell=48, d=32, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(key, (c, ell)),
            jax.random.uniform(jax.random.fold_in(key, 1), (ell,)),
            jax.random.normal(jax.random.fold_in(key, 2), (ell, d)))


def _fleet_args(n=3, ell=16, d=8, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(key, (n, ell, d)),
            jax.random.normal(jax.random.fold_in(key, 1), (n, ell)),
            jax.random.uniform(jax.random.fold_in(key, 2), (n, ell)))


def test_encode_parity_auto_cold_miss_is_default(tmp_cache):
    g, w, x = _encode_args()
    np.testing.assert_array_equal(
        np.asarray(en_ops.encode_parity(g, w, x, block="auto")),
        np.asarray(en_ops.encode_parity(g, w, x, block=_en.DEFAULT_BLOCK)))


def test_encode_parity_auto_hit_uses_stored_tile(tmp_cache):
    g, w, x = _encode_args()
    tile = (32, 16, 16)
    tmp_cache.store("encode", (64, 48, 32), kcommon.backend(), tile)
    assert kcommon.resolve_block("encode", (64, 48, 32), "auto",
                                 _en.DEFAULT_BLOCK) == tile
    np.testing.assert_array_equal(
        np.asarray(en_ops.encode_parity(g, w, x, block="auto")),
        np.asarray(en_ops.encode_parity(g, w, x, block=tile)))


def test_encode_fleet_auto_parity(tmp_cache):
    xs, ys, ws = _fleet_args()
    c = 32
    keys = jax.random.split(jax.random.PRNGKey(5), xs.shape[0])
    cold_a = en_ops.encode_fleet(keys, xs, ys, ws, c, block="auto")
    cold_d = en_ops.encode_fleet(keys, xs, ys, ws, c,
                                 block=_en.DEFAULT_BLOCK)
    for a, b in zip(cold_a, cold_d):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tile = (32, 8, 16)
    tmp_cache.store("encode", (c, xs.shape[1], xs.shape[2]),
                    kcommon.backend(), tile)
    hit_a = en_ops.encode_fleet(keys, xs, ys, ws, c, block="auto")
    hit_e = en_ops.encode_fleet(keys, xs, ys, ws, c, block=tile)
    for a, b in zip(hit_a, hit_e):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_encode_parity_prng_auto_parity(tmp_cache):
    _, w, x = _encode_args()
    c, key = 64, jax.random.PRNGKey(9)
    np.testing.assert_array_equal(
        np.asarray(en_ops.encode_parity_prng(key, w, x, c, block="auto")),
        np.asarray(en_ops.encode_parity_prng(key, w, x, c,
                                             block=_en.DEFAULT_BLOCK)))
    tile = (32, 16, 16)
    tmp_cache.store("encode_prng", (c, x.shape[0], x.shape[1]),
                    kcommon.backend(), tile)
    np.testing.assert_array_equal(
        np.asarray(en_ops.encode_parity_prng(key, w, x, c, block="auto")),
        np.asarray(en_ops.encode_parity_prng(key, w, x, c, block=tile)))


def test_encode_fleet_prng_auto_parity(tmp_cache):
    xs, ys, ws = _fleet_args()
    c, key = 32, jax.random.PRNGKey(3)
    cold_a = en_ops.encode_fleet_prng(key, xs, ys, ws, c, block="auto")
    cold_d = en_ops.encode_fleet_prng(key, xs, ys, ws, c,
                                      block=_en.DEFAULT_BLOCK)
    for a, b in zip(cold_a, cold_d):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lsq_gradient_auto_parity(tmp_cache):
    key = jax.random.PRNGKey(2)
    a = jax.random.normal(key, (96, 12))
    y = jax.random.normal(jax.random.fold_in(key, 1), (96,))
    beta = jax.random.normal(jax.random.fold_in(key, 2), (12,))
    np.testing.assert_array_equal(
        np.asarray(cg_ops.lsq_gradient(a, y, beta, block_m="auto")),
        np.asarray(cg_ops.lsq_gradient(a, y, beta,
                                       block_m=_cg.DEFAULT_BLOCK_M)))
    tmp_cache.store("coded_grad", (96, 12), kcommon.backend(), (64,))
    # 1-d tile families resolve to a plain int
    assert kcommon.resolve_block("coded_grad", (96, 12), "auto",
                                 _cg.DEFAULT_BLOCK_M) == 64
    np.testing.assert_array_equal(
        np.asarray(cg_ops.lsq_gradient(a, y, beta, block_m="auto")),
        np.asarray(cg_ops.lsq_gradient(a, y, beta, block_m=64)))


# ---------------------------------------------------------------------------
# perf-trend trajectory gate
# ---------------------------------------------------------------------------

def _bench_payload(us=1000.0, speedup=10.0):
    return {"schema": 1, "benchmark": "kernels",
            "gates": {"best_encode_tuned_speedup_x": speedup},
            "records": [{"name": "kernels/encode_auto", "us_per_call": us,
                         "derived": ""}]}


def _write(dirpath, payload):
    os.makedirs(dirpath, exist_ok=True)
    with open(os.path.join(dirpath, "BENCH_kernels.json"), "w") as f:
        json.dump(payload, f)


def test_perf_trend_classify_directions():
    assert perf_trend.classify("kernels/encode.us_per_call") == "lower"
    assert perf_trend.classify("gates.best_speedup_x") == "higher"
    assert perf_trend.classify("gates.sessions_per_s") == "higher"
    assert perf_trend.classify("gates.n_clients") is None


def test_perf_trend_classify_suffix_only_for_underscore_patterns():
    """'_s' must match only as a suffix: counts like n_samples stay
    ungated instead of being silently gated lower-is-better."""
    assert perf_trend.classify("gates.n_samples") is None
    assert perf_trend.classify("gates.n_sessions") is None
    assert perf_trend.classify("gates.wall_s") == "lower"


def test_perf_trend_identical_passes(tmp_path):
    base, new = str(tmp_path / "b"), str(tmp_path / "n")
    _write(base, _bench_payload())
    _write(new, _bench_payload())
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 0


def test_perf_trend_detects_regressions(tmp_path):
    """A synthetic regression (timing 3x worse, gate halved) must fail."""
    base, new = str(tmp_path / "b"), str(tmp_path / "n")
    _write(base, _bench_payload(us=1000.0, speedup=10.0))
    _write(new, _bench_payload(us=3000.0, speedup=5.0))
    result = perf_trend.compare(perf_trend.load_bench_dir(base),
                                perf_trend.load_bench_dir(new),
                                tol=0.60, gate_tol=0.25)
    assert len(result["regressions"]) == 2
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 1


def test_perf_trend_band_absorbs_noise(tmp_path):
    """Worsening WITHIN the band (timing +40% < 60%, gate -10% < 25%)
    passes; improvements always pass."""
    base, new = str(tmp_path / "b"), str(tmp_path / "n")
    _write(base, _bench_payload(us=1000.0, speedup=10.0))
    _write(new, _bench_payload(us=1400.0, speedup=9.0))
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 0
    _write(new, _bench_payload(us=100.0, speedup=100.0))
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 0


def test_perf_trend_env_tolerance_and_skip(tmp_path, monkeypatch):
    base, new = str(tmp_path / "b"), str(tmp_path / "n")
    _write(base, _bench_payload(us=1000.0))
    _write(new, _bench_payload(us=3000.0))
    # widening the timing band past the 3x regression -> pass
    monkeypatch.setenv("PERF_TREND_TOL", "5.0")
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 0
    monkeypatch.delenv("PERF_TREND_TOL")
    # ... or skipping the metric by glob
    monkeypatch.setenv("PERF_TREND_SKIP", "kernels/encode_auto*")
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 0


def test_perf_trend_missing_baseline_is_ok(tmp_path):
    empty, new = str(tmp_path / "b"), str(tmp_path / "n")
    os.makedirs(empty)
    _write(new, _bench_payload())
    assert perf_trend.main(["--baseline-dir", empty,
                            "--new-dir", new]) == 0


def test_perf_trend_baseline_nested_inside_new_dir(tmp_path):
    """CI layout: --new-dir is the workspace root and the baseline dir
    sits INSIDE it.  The new-dir scan must skip the baseline's own
    files, or it diffs the baseline against itself and a real
    regression passes silently."""
    root = str(tmp_path)
    base = os.path.join(root, "perf_baseline")
    _write(base, _bench_payload(us=1000.0, speedup=10.0))
    _write(root, _bench_payload(us=5000.0, speedup=2.0))  # regressed run
    new = perf_trend.load_bench_dir(root, exclude=base)
    assert new["kernels"]["records"][0]["us_per_call"] == 5000.0
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", root]) == 1


def test_perf_trend_recurses_into_artifact_subdirs(tmp_path):
    """Artifact downloads nest files under bench-<run>/ subdirs."""
    base = str(tmp_path / "b")
    _write(os.path.join(base, "bench-41"), _bench_payload(us=1000.0))
    new = str(tmp_path / "n")
    _write(new, _bench_payload(us=5000.0))
    assert perf_trend.main(["--baseline-dir", base,
                            "--new-dir", new]) == 1
