"""`solve_fleet` — the redundancy solve at fleet scale (1e5+ clients).

`repro.plan.solve_redundancy_batched` evaluates the full `(t_grid, n, L)`
expected-return tensor on ONE device per deadline probe; at n = 1e5 that
tensor (and its K-term retransmission mixture) no longer fits a sane
working set.  This module solves the SAME problem (identical per-device
expressions, identical monotone grid refinement) with the device axis

  * SHARDED over the local mesh (`launch.mesh.make_shard_mesh`): each
    shard owns n/D devices and `lax.psum` reassembles the aggregate best
    return, and
  * CHUNK-STREAMED within each shard: a `lax.scan` over fixed-size device
    chunks evaluates `(t_grid, chunk, L)` slabs, so peak memory is
    O(t_grid * chunk * L) per device regardless of n.

Invariants vs the batched solver (asserted by tests/test_fleet.py):

  * per-device expected returns are evaluated by the SAME expressions in
    the same float64 dtype (no float32 scout at fleet scale — the scout's
    saturation pathology is exactly what giant fleets hit);
  * the chosen loads are each device's independent argmax at t*, so they
    match the batched solver's loads exactly whenever t* agrees;
  * the aggregate return is reassociated (chunk partial sums + a psum
    tree instead of one flat sum), so t* may differ from the batched
    solver by the grid-refinement tolerance — NOT bit-for-bit.  Padded
    devices carry cap 0 and contribute exactly 0.0, as in the batched
    solver.

The load axis is bucketed to a power of two (floor 8) instead of the
batched solver's 64-wide bucket: fleet-scale clients hold small shards
(the whole point of coding over many weak devices), so a tight L keeps
the slab small.  `srv_weight` and `edge_chunks` behave exactly as in
`PlanRequest`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.delay_model import total_cdf
from repro.core.redundancy import RedundancyPlan
from repro.plan.solver import (GRID_POINTS, MAX_DOUBLINGS, MAX_ROUNDS,
                               PlanRequest, _k_terms)

# Device-chunk length of the streamed evaluation: one slab is
# (GRID_POINTS, CHUNK, L) float64.
CHUNK = 4096


def _pow2_bucket(value: int, floor: int = 8) -> int:
    out = floor
    while out < value:
        out *= 2
    return out


@functools.partial(jax.jit,
                   static_argnames=("edge_chunks", "n_shards", "chunk"))
def _solve_fleet_grid(a, mu, tau, p, caps, srv_a, srv_mu, srv_w, srv_cap,
                      target, t_hi0, eps_rel, ell_e, ell_s, ks, frac, *,
                      edge_chunks=1, n_shards=1, chunk=CHUNK):
    """Sharded single-request grid solve.  All inputs float64.

    a/mu/tau/p/caps: (n_pad,) edge params, n_pad = n_shards * k * chunk
    srv_*/target/t_hi0/eps_rel: scalars   ell_e: (L,)  ell_s: (Ls,)
    ks: (K,) retransmission counts        frac: (T,) refinement fractions

    Returns (t_star, loads (n_pad,), s_load, agg, feasible).  The
    per-device expressions mirror `repro.plan.solver._solve_grid` term for
    term (shifted-exp CDF, negative-binomial mixture with the pmf_total
    saturation snap, partial-return chunking) — only the reduction over
    devices is restructured into chunk partials + a psum tree.
    """
    from repro.launch.mesh import make_shard_mesh

    mesh = make_shard_mesh()
    dtype = a.dtype
    n_k = ks.shape[0]
    snap_tol = 1e-13

    s_ok = ell_s <= srv_cap                                      # (Ls,)

    def varying(x):
        """Loop carries that accumulate per-shard values start varying."""
        return jax.lax.pcast(x, "shards", to="varying")

    def _shifted_exp_cdf(gamma_, s_):
        return jnp.where(
            s_ > 0.0,
            -jnp.expm1(-jnp.minimum(gamma_ * jnp.maximum(s_, 0.0), 700.0)),
            0.0)

    def server_returns(t):
        """Weighted server E[R(t; ell)].  t: (T',) -> (T', Ls)."""
        s = t[:, None] - ell_s[None, :] * srv_a
        cdf = _shifted_exp_cdf(srv_mu / jnp.maximum(ell_s, 1.0), s)
        cdf = jnp.where(ell_s > 0.0, cdf, (t[:, None] >= 0.0).astype(dtype))
        return jnp.where(s_ok[None, :], srv_w * ell_s * cdf, -jnp.inf)

    def chunk_returns(t, prm):
        """One device chunk's masked return grid.  t: (T',) ->
        (T', chunk, L) — the streamed slab of the batched solver's
        (t_grid, n, L) tensor."""
        a_c, mu_c, tau_c, p_c, caps_c = prm                     # (chunk,)
        shift = ell_e[None, :] * a_c[:, None]                   # (chunk, L)
        gamma = mu_c[:, None] / jnp.maximum(ell_e, 1.0)         # (chunk, L)
        load_ok = ell_e[None, :] <= caps_c[:, None]             # (chunk, L)
        has_comm = tau_c > 0.0                                  # (chunk,)
        pmf = (ks - 1.0) * p_c[:, None] ** (ks - 2.0) \
            * (1.0 - p_c[:, None]) ** 2                         # (chunk, K)
        pmf_total = jax.lax.fori_loop(
            0, n_k, lambda i, acc: acc + pmf[:, i],
            jnp.zeros_like(a_c))                                # (chunk,)
        snap_ok = pmf_total >= 1.0 - snap_tol

        def _load_cdf(t_res):
            """(T', chunk) residual times -> (T', chunk, L) per-load CDF."""
            if edge_chunks == 1:
                s = t_res[..., None] - shift[None]
                cdf = _shifted_exp_cdf(gamma[None], s)
            else:
                def add_q(j, acc):
                    fq = (jnp.asarray(j, dtype) + 1.0) / edge_chunks
                    s = t_res[..., None] - fq * shift[None]
                    return acc + _shifted_exp_cdf(gamma[None], s)
                cdf = jax.lax.fori_loop(
                    0, edge_chunks, add_q,
                    varying(jnp.zeros(t_res.shape + (ell_e.shape[0],),
                                      dtype=dtype)))
                cdf = cdf / edge_chunks
            return jnp.where(ell_e > 0.0, cdf,
                             (t_res[..., None] >= 0.0).astype(dtype))

        def add_k(i, acc):
            t_res = t[:, None] - ks[i] * tau_c[None, :]         # (T', chunk)
            return acc + pmf[None, :, i, None] * _load_cdf(t_res)

        mix = jax.lax.fori_loop(
            0, n_k, add_k,
            varying(jnp.zeros(t.shape + (a_c.shape[0], ell_e.shape[0]),
                              dtype=dtype)))
        mix = jnp.where(
            jnp.logical_and(mix >= pmf_total[None, :, None],
                            snap_ok[None, :, None]),
            jnp.ones((), dtype=dtype), mix)
        nocomm = _load_cdf(jnp.broadcast_to(t[:, None],
                                            t.shape + (a_c.shape[0],)))
        mix = jnp.where(has_comm[None, :, None], mix, nocomm)
        return jnp.where(load_ok[None], ell_e * mix, -jnp.inf)

    def solve(a_l, mu_l, tau_l, p_l, caps_l):
        """Per-shard body: full search over replicated control flow, with
        chunk-streamed local evaluation and psum'd aggregates."""
        # (n_chunks, 5, chunk): one scan step consumes one device chunk's
        # five parameter rows
        prm_stack = jnp.stack(
            [x.reshape(-1, chunk)
             for x in (a_l, mu_l, tau_l, p_l, caps_l)], axis=1)

        def local_best_sum(t):
            """Sum over this shard's devices of max-over-L return: (T',)."""
            def step(acc, prm_c):
                ev = chunk_returns(t, tuple(prm_c))     # (T', chunk, L)
                return acc + ev.max(axis=-1).sum(axis=-1), None
            out, _ = jax.lax.scan(step, varying(jnp.zeros_like(t)),
                                  prm_stack)
            return out

        def best_agg(t):
            """(T',) aggregate best return across the whole fleet."""
            edge = jax.lax.psum(local_best_sum(t), "shards")
            return edge + server_returns(t).max(axis=-1)

        # --- bracket expansion (scalar mirror of _solve_grid._search) ------
        agg0 = best_agg(t_hi0[None])[0]

        def b_cond(st):
            _, _, agg_c, i = st
            return jnp.logical_and(i < MAX_DOUBLINGS, agg_c < target)

        def b_body(st):
            t_hi_c, step, _, i = st
            t_new = t_hi_c + step
            return (t_new, 2.0 * step, best_agg(t_new[None])[0], i + 1)

        t_hi, _, agg_hi, _ = jax.lax.while_loop(
            b_cond, b_body, (t_hi0, t_hi0, agg0, jnp.asarray(0)))
        feasible = agg_hi >= target

        # --- monotone grid refinement --------------------------------------
        def _active(t_lo_c, t_hi_c):
            wide = (t_hi_c - t_lo_c) > eps_rel * jnp.maximum(t_hi_c, 1e-12)
            return jnp.logical_and(wide, feasible)

        def r_cond(st):
            t_lo_c, t_hi_c, r = st
            return jnp.logical_and(r < MAX_ROUNDS, _active(t_lo_c, t_hi_c))

        def r_body(st):
            t_lo_c, t_hi_c, r = st
            grid = t_lo_c + frac * (t_hi_c - t_lo_c)
            grid = grid.at[-1].set(t_hi_c)  # exact upper edge: invariant
            ok = best_agg(grid) >= target
            idx = jnp.argmax(ok)
            hi_new = grid[idx]
            lo_new = jnp.where(idx == 0, t_lo_c,
                               grid[jnp.maximum(idx - 1, 0)])
            act = _active(t_lo_c, t_hi_c)
            return (jnp.where(act, lo_new, t_lo_c),
                    jnp.where(act, hi_new, t_hi_c), r + 1)

        _, t_star, _ = jax.lax.while_loop(
            r_cond, r_body, (jnp.zeros_like(t_hi), t_hi, jnp.asarray(0)))

        # --- extraction at t* ----------------------------------------------
        def extract(_, prm_c):
            ev = chunk_returns(t_star[None], tuple(prm_c))[0]   # (chunk, L)
            loads_c = jnp.argmax(ev, axis=-1)
            best_c = jnp.take_along_axis(
                ev, loads_c[:, None], axis=-1)[:, 0]
            return None, (loads_c, best_c.sum())

        _, (loads_l, best_sums) = jax.lax.scan(extract, None, prm_stack)
        edge_best = jax.lax.psum(best_sums.sum(), "shards")
        sv = server_returns(t_star[None])[0]                    # (Ls,)
        s_load = jnp.argmax(sv)
        agg = edge_best + sv[s_load]
        return (loads_l.reshape(-1), t_star, s_load, agg, feasible)

    spec_n = P("shards")
    fn = jax.shard_map(
        solve, mesh=mesh,
        in_specs=(spec_n,) * 5,
        out_specs=(spec_n, P(), P(), P(), P()))
    return fn(a, mu, tau, p, caps)


def solve_fleet(request: PlanRequest, eps_rel: float = 1e-3,
                grid_points: int = GRID_POINTS,
                chunk: int = CHUNK) -> RedundancyPlan:
    """Solve one fleet-scale redundancy problem, sharded + streamed.

    Accepts the same `PlanRequest` as the batched solver (srv_weight and
    edge_chunks included) and returns the same `RedundancyPlan`; see the
    module docstring for the numerical invariants vs
    `solve_redundancy_batched`.
    """
    req = request
    n = req.edge.n
    n_shards = len(jax.devices())
    chunk = max(8, min(int(chunk), _pow2_bucket(n)))
    step = n_shards * chunk
    n_pad = -(-n // step) * step

    def pad(vec, fill):
        out = np.full(n_pad, fill, dtype=np.float64)
        out[:n] = vec
        return out

    a = pad(req.edge.a, 1.0)
    mu = pad(req.edge.mu, 1.0)
    tau = pad(req.edge.tau, 0.0)
    p = pad(req.edge.p, 0.0)
    caps = pad(req.data_sizes.astype(np.float64), 0.0)

    l_edge = _pow2_bucket(int(req.data_sizes.max()) + 1)
    l_srv = _pow2_bucket(req.server_cap + 1)
    n_k = _k_terms(float(req.edge.p.max()), tol=1e-12)
    frac = np.arange(1, grid_points + 1, dtype=np.float64) / grid_points
    t_hi0 = req.t_hi if req.t_hi is not None else req.default_t_hi()

    with jax.enable_x64(True):
        out = _solve_fleet_grid(
            a, mu, tau, p, caps,
            np.float64(req.server.a[0]), np.float64(req.server.mu[0]),
            np.float64(req.srv_weight), np.float64(req.server_cap),
            np.float64(req.m), np.float64(t_hi0), np.float64(eps_rel),
            np.arange(l_edge, dtype=np.float64),
            np.arange(l_srv, dtype=np.float64),
            np.arange(2, 2 + n_k, dtype=np.float64), frac,
            edge_chunks=int(req.edge_chunks), n_shards=n_shards,
            chunk=chunk)
        loads_pad, t_star, s_load, agg, feasible = \
            (np.asarray(o) for o in out)

    if not bool(feasible):
        raise RuntimeError(
            "cannot reach the aggregate expected return target — the "
            f"fleet cannot return the points in finite time: target "
            f"{req.m}, best achievable {float(agg):.1f}")

    dev_loads = loads_pad[:n].astype(np.int64)
    c = int(req.fixed_c) if req.fixed_c is not None else int(s_load)
    p_return = np.append(
        total_cdf(req.edge, dev_loads, float(t_star)),
        total_cdf(req.server, np.array([float(s_load)]), float(t_star)))
    return RedundancyPlan(loads=dev_loads, c=c, t_star=float(t_star),
                          p_return=p_return, expected_agg=float(agg),
                          loads_cap_total=req.m)
