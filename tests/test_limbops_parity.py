"""Kernel <-> reference parity for the batched limb-op dispatch layer.

Exercises the Pallas `mul_mod/add_mod/sub_mod`, the inner product
`dot_mod` and forward/inverse NTT
kernels (interpret mode off-TPU) against the pure-jnp `*_ref` oracles
through `core/limbops.LimbOps`, across several limb counts, batch
shapes, non-tile-aligned lengths, and edge values (0, q-1).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.limbops import LimbOps, pallas_supported, resolve_backend
from repro.core.mathutil import find_ntt_primes
from repro.core.params import make_params

POINTWISE = ("mul", "add", "sub")


def _rand(rng, primes, shape_prefix, n):
    k = len(primes)
    return jnp.asarray(
        rng.integers(0, np.array(primes)[:, None], shape_prefix + (k, n)))


@pytest.fixture(scope="module")
def param_grid():
    """(params, ref LimbOps, pallas LimbOps) for several (n, t, k)."""
    out = []
    for n, t, k in [(64, 257, 1), (128, 257, 2), (256, 7681, 3)]:
        p = make_params(n=n, t=t, k=k)
        out.append((p,
                    LimbOps(p.Q, backend="ref"),
                    LimbOps(p.Q, backend="pallas", interpret=True)))
    return out


def test_pallas_backend_resolves(param_grid):
    for p, _, pal in param_grid:
        assert pal.backend == "pallas", p.Q.primes


@pytest.mark.parametrize("op", POINTWISE)
def test_pointwise_parity(param_grid, op):
    rng = np.random.default_rng(7)
    for p, ref, pal in param_grid:
        a = _rand(rng, p.Q.primes, (), p.n)
        b = _rand(rng, p.Q.primes, (), p.n)
        got = getattr(pal, op)(a, b)
        exp = getattr(ref, op)(a, b)
        assert np.array_equal(np.asarray(got), np.asarray(exp)), (op, p.n)


@pytest.mark.parametrize("op", POINTWISE)
@pytest.mark.parametrize("batch", [(2,), (3, 2)])
def test_pointwise_parity_batched(param_grid, op, batch):
    """Batched (.., k, n) inputs match both the ref and the per-slice loop."""
    rng = np.random.default_rng(11)
    p, ref, pal = param_grid[-1]
    a = _rand(rng, p.Q.primes, batch, p.n)
    b = _rand(rng, p.Q.primes, batch, p.n)
    got = np.asarray(getattr(pal, op)(a, b))
    exp = np.asarray(getattr(ref, op)(a, b))
    assert np.array_equal(got, exp)
    flat_a = a.reshape((-1,) + a.shape[-2:])
    flat_b = b.reshape((-1,) + b.shape[-2:])
    loop = np.stack([np.asarray(getattr(pal, op)(x, y))
                     for x, y in zip(flat_a, flat_b)])
    assert np.array_equal(got.reshape(loop.shape), loop)


@pytest.mark.parametrize("batch", [(2,), (3, 2)], ids=["ciphertext", "blocks"])
def test_dot_parity(param_grid, batch):
    """The 32-term inner product: one kernel call over a ciphertext
    (2, k, n) or a batch of blocks (3, 2, k, n) equals the int64 ref,
    with zero (padding) and maximal (< 2^17) coefficients among them."""
    rng = np.random.default_rng(17)
    for p, ref, pal in param_grid:
        acc = _rand(rng, p.Q.primes, batch, p.n)
        datas = [_rand(rng, p.Q.primes, batch, p.n) for _ in range(32)]
        cs = jnp.asarray(np.r_[rng.integers(0, 1 << 17, 30), 0, (1 << 17) - 1])
        got = np.asarray(pal.dot(acc, datas, cs))
        assert got.shape == acc.shape
        assert np.array_equal(got, np.asarray(ref.dot(acc, datas, cs))), (p.n, batch)


def test_pointwise_edge_values(param_grid):
    """0 and q-1 lanes: the Barrett/csub corner cases."""
    for p, ref, pal in param_grid:
        k, n = len(p.Q.primes), p.n
        qcol = np.array(p.Q.primes, dtype=np.int64)[:, None]
        zeros = jnp.zeros((k, n), dtype=jnp.int64)
        qm1 = jnp.asarray(np.broadcast_to(qcol - 1, (k, n)).copy())
        for a, b in [(zeros, zeros), (zeros, qm1), (qm1, zeros), (qm1, qm1)]:
            for op in POINTWISE:
                got = getattr(pal, op)(a, b)
                exp = getattr(ref, op)(a, b)
                assert np.array_equal(np.asarray(got), np.asarray(exp)), op
        # (q-1)^2 is the largest Barrett product
        exp_mul = np.asarray((np.asarray(qm1) * np.asarray(qm1)) % qcol)
        assert np.array_equal(np.asarray(pal.mul(qm1, qm1)), exp_mul)


def test_pointwise_non_tile_aligned():
    """Column tiles that do not divide n: the grid's ragged last tile."""
    from repro.kernels.modops.modops import add_mod_pallas, mul_mod_pallas, sub_mod_pallas
    from repro.kernels.modops import ref as mod_ref
    from repro.kernels.u32 import barrett_precompute
    n, rows = 384, 3           # 384 = 3 x 128: not a power of two
    primes = find_ntt_primes(64, 30, rows)
    q64 = jnp.asarray(np.array(primes, dtype=np.int64))
    qu = jnp.asarray(np.array(primes, dtype=np.uint32))[:, None]
    mu = jnp.asarray(np.array([barrett_precompute(q) for q in primes],
                              dtype=np.uint32))[:, None]
    rng = np.random.default_rng(5)
    a = rng.integers(0, np.array(primes)[:, None], (rows, n))
    b = rng.integers(0, np.array(primes)[:, None], (rows, n))
    au, bu = jnp.asarray(a, dtype=jnp.uint32), jnp.asarray(b, dtype=jnp.uint32)
    ai, bi = jnp.asarray(a), jnp.asarray(b)
    for tile in (256, 96):     # 384 % 256 != 0; 384 % 96 == 0
        got = mul_mod_pallas(au, bu, qu, mu, tile=tile).astype(jnp.int64)
        assert np.array_equal(np.asarray(got),
                              np.asarray(mod_ref.mul_mod_ref(ai, bi, q64))), tile
        got = add_mod_pallas(au, bu, qu, tile=tile).astype(jnp.int64)
        assert np.array_equal(np.asarray(got),
                              np.asarray(mod_ref.add_mod_ref(ai, bi, q64))), tile
        got = sub_mod_pallas(au, bu, qu, tile=tile).astype(jnp.int64)
        assert np.array_equal(np.asarray(got),
                              np.asarray(mod_ref.sub_mod_ref(ai, bi, q64))), tile


@pytest.mark.parametrize("batch", [(), (2,), (4,)])
def test_ntt_roundtrip_parity(param_grid, batch):
    rng = np.random.default_rng(13)
    for p, ref, pal in param_grid:
        a = _rand(rng, p.Q.primes, batch, p.n)
        fwd_p, fwd_r = pal.ntt(a), ref.ntt(a)
        assert np.array_equal(np.asarray(fwd_p), np.asarray(fwd_r)), p.n
        inv_p, inv_r = pal.intt(fwd_p), ref.intt(fwd_r)
        assert np.array_equal(np.asarray(inv_p), np.asarray(inv_r))
        assert np.array_equal(np.asarray(inv_p), np.asarray(a))


def test_ntt_edge_values(param_grid):
    for p, ref, pal in param_grid[:1]:
        k, n = len(p.Q.primes), p.n
        qcol = np.array(p.Q.primes, dtype=np.int64)[:, None]
        for arr in (np.zeros((k, n), dtype=np.int64),
                    np.broadcast_to(qcol - 1, (k, n)).copy()):
            a = jnp.asarray(arr)
            assert np.array_equal(np.asarray(pal.ntt(a)), np.asarray(ref.ntt(a)))
            assert np.array_equal(np.asarray(pal.intt(a)), np.asarray(ref.intt(a)))


def test_both_bases_in_window_and_outside_raises():
    """Q and the HPS auxiliary base P both sit in the Barrett window, so
    both run the kernels; asking the kernels for a base outside the
    window raises instead of quietly running the reference."""
    p = make_params(n=64, t=257, k=1)
    assert pallas_supported(p.Q.primes) and pallas_supported(p.P.primes)
    assert LimbOps(p.P, backend="pallas").backend == "pallas"
    assert LimbOps(p.Q, backend="pallas").backend == "pallas"
    wide = find_ntt_primes(64, 31, 2)
    assert not pallas_supported(wide)
    with pytest.raises(ValueError, match="Barrett|2\\^28"):
        resolve_backend("pallas", wide)
    assert resolve_backend("ref", wide) == "ref"


def test_dot_kernel_refuses_coefficients_past_2_17():
    """The inner-product kernel's exact sum holds for coefficients below
    2^17; a plaintext modulus above that raises on the kernel backend
    instead of wrapping, and the int64 reference still runs it."""
    from repro.core.bfv import BFVContext
    p = make_params(n=64, t=786433, k=1)
    x = [jnp.ones((2, 1, 64), dtype=jnp.int64)]
    with pytest.raises(ValueError, match="2\\^17"):
        BFVContext(p, backend="pallas").dot_scalars(x, [p.t - 1])
    got = BFVContext(p, backend="ref").dot_scalars(x, [p.t - 1])
    assert np.array_equal(np.asarray(got), (p.t - 1) % np.asarray(p.Q.q)[:, None]
                          * np.ones((2, 1, 64), dtype=np.int64))


def test_resolve_backend_flags():
    primes_ok = find_ntt_primes(64, 30, 2)
    assert resolve_backend("ref", primes_ok) == "ref"
    assert resolve_backend("pallas", primes_ok) == "pallas"
    assert resolve_backend("auto", primes_ok) in ("ref", "pallas")
    with pytest.raises(ValueError):
        resolve_backend("cuda", primes_ok)
