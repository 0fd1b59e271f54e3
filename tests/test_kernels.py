"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode + uint32 modular arithmetic properties (hypothesis)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.params import make_params
from repro.kernels import u32

PRIME30 = 1073479681  # 30-bit NTT prime


@given(st.integers(0, PRIME30 - 1), st.integers(0, PRIME30 - 1))
@settings(max_examples=200, deadline=None)
def test_barrett_mulmod_property(a, b):
    mu = u32.barrett_precompute(PRIME30)
    got = int(u32.barrett_mulmod(jnp.uint32(a), jnp.uint32(b),
                                 jnp.uint32(PRIME30), jnp.uint32(mu)))
    assert got == a * b % PRIME30


@given(st.integers(0, PRIME30 - 1), st.integers(1, PRIME30 - 1))
@settings(max_examples=200, deadline=None)
def test_shoup_mulmod_property(a, w):
    ws = u32.shoup_precompute(w, PRIME30)
    got = int(u32.shoup_mulmod(jnp.uint32(a), jnp.uint32(w),
                               jnp.uint32(ws), jnp.uint32(PRIME30)))
    assert got == a * w % PRIME30


@given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
@settings(max_examples=200, deadline=None)
def test_mulhi_property(a, b):
    got = int(u32.mulhi_u32(jnp.uint32(a), jnp.uint32(b)))
    assert got == (a * b) >> 32


@given(st.integers(0, PRIME30 - 1),
       st.lists(st.tuples(st.integers(0, PRIME30 - 1), st.integers(0, 2**17 - 1)),
                max_size=40))
@settings(max_examples=200, deadline=None)
def test_dot_mod_property(acc, pairs):
    mu = u32.barrett_precompute(PRIME30)
    got = int(u32.dot_mod(jnp.uint32(acc), [jnp.uint32(d) for d, _ in pairs],
                          [c for _, c in pairs], jnp.uint32(PRIME30), jnp.uint32(mu)))
    assert got == (acc + sum(d * c for d, c in pairs)) % PRIME30


@pytest.mark.parametrize("n,k", [(64, 1), (128, 2), (256, 3), (512, 2)])
def test_ntt_kernel_sweep(n, k):
    from repro.kernels.ntt import ops as ntt_ops
    from repro.kernels.ntt import ref as ntt_ref
    t = {64: 257, 128: 257, 256: 7681, 512: 12289}[n]
    p = make_params(n=n, t=t, k=k)
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.integers(0, np.array(p.Q.primes)[:, None], (k, n)))
    got = ntt_ops.ntt_fwd(a, p.Q)
    exp = ntt_ref.ntt_fwd_ref(a, jnp.asarray(p.Q.psi_rev), jnp.asarray(p.Q.q))
    assert np.array_equal(np.asarray(got), np.asarray(exp))
    back = ntt_ops.ntt_inv(got, p.Q)
    assert np.array_equal(np.asarray(back), np.asarray(a))


@pytest.mark.parametrize("rows,n", [(1, 128), (3, 256), (6, 512)])
def test_modops_kernel_sweep(rows, n):
    from repro.core.mathutil import find_ntt_primes
    from repro.kernels.modops import ops as mod_ops
    from repro.kernels.modops import ref as mod_ref
    primes = tuple(find_ntt_primes(n, 30, rows))
    q = jnp.asarray(np.array(primes, dtype=np.int64))
    rng = np.random.default_rng(rows * n)
    a = jnp.asarray(rng.integers(0, np.array(primes)[:, None], (rows, n)))
    b = jnp.asarray(rng.integers(0, np.array(primes)[:, None], (rows, n)))
    for op, ref in [(mod_ops.mul_mod, mod_ref.mul_mod_ref),
                    (mod_ops.add_mod, mod_ref.add_mod_ref),
                    (mod_ops.sub_mod, mod_ref.sub_mod_ref)]:
        got = op(a, b, primes)
        assert np.array_equal(np.asarray(got), np.asarray(ref(a, b, q)))


@pytest.mark.parametrize("case", ["random", "max", "zero_coeffs", "padded"])
@pytest.mark.parametrize("rows,n", [(30, 1024), (31, 2048), (60, 4096)])
def test_dot_kernel_sweep(rows, n, case):
    """The inner-product kernel (32 terms, as the engine runs it) against
    a numpy int64 reference; rows 31 is off the 8-row tiling, 60 is one
    ciphertext at k = 30.  Edge cases: every term and acc at q - 1 with
    c = t - 1 = 2^16; all coefficients zero; the last terms padded with
    a repeat of the first at coefficient zero, as dot_scalars pads."""
    from repro.core.mathutil import find_ntt_primes
    from repro.kernels.modops.modops import dot_mod_pallas
    from repro.kernels.modops.ref import dot_mod_ref
    T = 32
    primes = find_ntt_primes(n, 30, rows)
    q = np.array(primes, dtype=np.int64)[:, None]
    rng = np.random.default_rng(rows * n)
    acc = rng.integers(0, q, (rows, n))
    terms = [rng.integers(0, q, (rows, n)) for _ in range(T)]
    cs = rng.integers(0, 65537, T)
    if case == "max":
        acc = np.broadcast_to(q - 1, (rows, n))
        terms = [acc] * T
        cs = np.full(T, 65536)
    elif case == "zero_coeffs":
        cs = np.zeros(T, dtype=np.int64)
    elif case == "padded":
        terms = terms[:20] + [terms[0]] * 12
        cs[20:] = 0
    exp = dot_mod_ref(acc, terms, cs, q[:, 0])          # numpy int64
    mu = np.array([u32.barrett_precompute(p) for p in primes], dtype=np.uint32)[:, None]
    got = dot_mod_pallas(jnp.asarray(acc, dtype=jnp.uint32),
                         [jnp.asarray(d, dtype=jnp.uint32) for d in terms],
                         jnp.asarray(cs, dtype=jnp.uint32),
                         jnp.asarray(q, dtype=jnp.uint32), jnp.asarray(mu))
    assert np.array_equal(np.asarray(got).astype(np.int64), exp)


@pytest.mark.parametrize("rows,n,chunk", [(2, 256, None), (4, 1024, None),
                                          (3, 512, 8)])
def test_rotate_reduce_sweep(rows, n, chunk):
    from repro.kernels.rotate_reduce import ops as rr_ops
    from repro.kernels.rotate_reduce import ref as rr_ref
    rng = np.random.default_rng(n)
    x = rng.integers(0, 65537, (rows, n))
    got = rr_ops.rotate_reduce(x, 65537, chunk=chunk)
    exp = rr_ref.rotate_reduce_ref(jnp.asarray(x, dtype=jnp.int32), 65537,
                                   chunk=chunk)
    assert np.array_equal(np.asarray(got), np.asarray(exp))
    if chunk is None:
        assert int(np.asarray(got)[0, 0]) == int(x[0].sum() % 65537)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kwargs", [dict(causal=True),
                                    dict(causal=True, window=32),
                                    dict(causal=True, softcap=50.0),
                                    dict(causal=False)])
def test_flash_attention_sweep(dtype, kwargs):
    from repro.kernels.flash_attn import ops as fa_ops
    from repro.kernels.flash_attn.ref import attention_ref
    B, H, Hkv, S, D = 2, 4, 2, 128, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, D), dtype)
    got = fa_ops.mha(q, k, v, **kwargs)
    kr = jnp.repeat(k, H // Hkv, axis=1).reshape(B * H, S, D)
    vr = jnp.repeat(v, H // Hkv, axis=1).reshape(B * H, S, D)
    exp = attention_ref(q.reshape(B * H, S, D), kr, vr, **kwargs)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = float(jnp.abs(got.astype(jnp.float32)
                        - exp.reshape(B, H, S, D).astype(jnp.float32)).max())
    assert err < tol, err
