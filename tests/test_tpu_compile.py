"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: block shapes off the (8, 128) tiling, contractions
Mosaic cannot express, more VMEM than a kernel may use.  Here each kernel
is lowered and compiled for one chip of a described `v5e:2x2` at the
widths the repo runs — §IV (d = 500), the widest RFF width
`fig_nonlinear` uses (512) and FEMNIST's 784 features — and the compiled
program must hold the kernel (`tpu_custom_call`).  One test also
compiles a whole session engine (kernel inside `shard_map`, `lax.map`
and `lax.scan`, varying-axis checks on) for the described chip.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off while these compiles run.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.encode import encode as enc_k
from repro.kernels.round_grad import round_grad as rg_k

M, C, ELL, TIERS = 7200, 2016, 300, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _f32(*shape):
    return (shape, jnp.float32)


ROUND_GRAD = {
    "masked": (lambda x, y, w, b: rg_k.masked_round_gradient(x, y, w, b),
               lambda d: [_f32(M, d), _f32(M), _f32(M), _f32(d)]),
    "coded": (lambda x, y, w, xp, yp, wp, b: rg_k.coded_round_gradient(
        x, y, w, xp, yp, wp, b),
        lambda d: [_f32(M, d), _f32(M), _f32(M), _f32(C, d), _f32(C),
                   _f32(C), _f32(d)]),
    "tier": (lambda x, y, w, t, b: rg_k.tier_masked_round_gradient(
        x, y, w, t, b),
        lambda d: [_f32(M, d), _f32(M), _f32(M), _f32(TIERS, M), _f32(d)]),
}


@pytest.mark.parametrize("d", [500, 512, 784])
@pytest.mark.parametrize("variant", sorted(ROUND_GRAD))
def test_round_grad_compiles_for_v5e(one_chip, variant, d):
    fn, shapes = ROUND_GRAD[variant]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes(d))


@pytest.mark.parametrize("kind", ["normal", "bernoulli"])
def test_encode_parity_prng_compiles_for_v5e(one_chip, kind):
    text = _compiled_text(
        lambda k, w, x: enc_k.encode_parity_prng(k, w, x, C, kind=kind),
        one_chip, ((2,), jnp.uint32), _f32(ELL), _f32(ELL, 500))
    assert "tpu_custom_call" in text


def test_encode_parity_compiles_for_v5e(one_chip):
    text = _compiled_text(enc_k.encode_parity, one_chip,
                          _f32(C, ELL), _f32(ELL), _f32(ELL, 500))
    assert "tpu_custom_call" in text


def test_session_engine_compiles_with_kernels(one_chip, topo, monkeypatch):
    """The real sweep engine of a 2-tier `HierarchicalCFL(CodedFL)`
    session, built for one described chip: the tier kernel runs inside
    `shard_map` + `lax.map` + `lax.scan` with the varying-axis checks
    on.  The backend probes are steered to "tpu" here, in the test, so
    the engine takes its chip branch."""
    import repro.api.session as session_mod
    from repro.api import CodedFL, Session, TrainData
    from repro.fleet import FleetTopology, HierarchicalCFL
    from repro.kernels import common
    from repro.kernels.round_grad import ops as rg_ops
    from repro.sim.network import paper_fleet

    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]), ("lanes",))
    monkeypatch.setattr(common, "backend", lambda: "tpu")
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    monkeypatch.setattr(rg_ops, "on_tpu", lambda: True)

    built = []
    build = session_mod._build_engine

    def build_for_chip(strategy, state, data, _host_mesh, shared, args):
        def shapes(tree, spec):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)
        built.append(build(strategy, state, data, mesh, shapes(shared, P()),
                           shapes(args, P("lanes"))))
        raise StopIteration

    monkeypatch.setattr(session_mod, "_build_engine", build_for_chip)
    n, ell, d = 12, 60, 40
    fleet = paper_fleet(0.2, 0.2, seed=0, n=n, d=d)
    data = TrainData.linreg(jax.random.PRNGKey(0), n, ell, d)
    strategy = HierarchicalCFL(
        base=CodedFL(key=jax.random.PRNGKey(1), fixed_c=int(0.28 * n * ell)),
        topology=FleetTopology.uniform(n, 2))
    with pytest.raises(StopIteration):
        Session(strategy=strategy, fleet=fleet, lr=0.05, epochs=5).run(
            data, rng=np.random.default_rng(0))
    text = built[0].as_text()
    assert "tpu_custom_call" in text
