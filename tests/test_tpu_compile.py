"""The main-path kernels compile for a TPU v5e at the paper's size.

Interpret mode runs anywhere but accepts kernels the TPU compiler
refuses (block shapes off the (8, 128) tiling, unsupported in-kernel
shape casts and rolls).  These tests lower and compile each kernel with
`interpret=False` for a *described* v5e — no chip needed — at
n = 32,768 with 30 and 32 rows (the RNS bases Q and P of the paper
set) and 31 (a row count off the 8-row tiling).  The topology is described inside a module fixture, never at
import time: only one process may load the TPU compiler library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as the engine runs the kernels)

N = 32768
LOG_N = N.bit_length() - 1
ROWS = (30, 31, 32)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("op", ["mul", "add", "sub"])
def test_modops_compiles(one_chip, rows, op):
    from repro.kernels.modops import modops
    a = _u32((rows, N), one_chip)
    col = _u32((rows, 1), one_chip)
    if op == "mul":
        fn = lambda a, b, q, mu: modops.mul_mod_pallas(a, b, q, mu, interpret=False)
        _compile(fn, a, a, col, col)
    else:
        kern = getattr(modops, f"{op}_mod_pallas")
        _compile(lambda a, b, q: kern(a, b, q, interpret=False), a, a, col)


@pytest.mark.parametrize("rows", ROWS + (60,))
def test_dot_compiles(one_chip, rows):
    """The 32-term inner product; 60 rows is one ciphertext at k = 30."""
    from repro.kernels.modops import modops
    a = _u32((rows, N), one_chip)
    col = _u32((rows, 1), one_chip)
    fn = lambda cs, acc, q, mu, *ts: modops.dot_mod_pallas(acc, ts, cs, q, mu,
                                                          interpret=False)
    _compile(fn, _u32((32,), one_chip), a, col, col, *[a] * 32)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_ntt_compiles(one_chip, rows, inverse):
    from repro.kernels.ntt import ntt
    R, L = ntt.tile_shape(N)
    tab = _u32((rows, LOG_N, R, L), one_chip)
    const = _u32((rows, 1, L), one_chip)
    a = _u32((2 * rows, N), one_chip)          # B = 2 blocks of `rows` limbs
    if inverse:
        fn = lambda a, w, ws, q, ni, nis: ntt.ntt_inv_pallas(
            a, w, ws, q, ni, nis, interpret=False)
        _compile(fn, a, tab, tab, const, const, const)
    else:
        fn = lambda a, w, ws, q: ntt.ntt_fwd_pallas(a, w, ws, q, interpret=False)
        _compile(fn, a, tab, tab, const)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("chunk", [None, 8])
def test_rotate_reduce_compiles(one_chip, rows, chunk):
    from repro.kernels.rotate_reduce.rotate_reduce import rotate_reduce_pallas
    x = jax.ShapeDtypeStruct((rows, N), jnp.int32, sharding=one_chip)
    _compile(lambda x: rotate_reduce_pallas(x, 65537, chunk=chunk,
                                            interpret=False), x)


def test_lane_program_on_data_mesh_has_no_collective(v5e_2x2):
    """On a 4-chip data mesh the multiply runs under shard_map, each
    chip on its own lanes (core/bfv.py `_lane_program`): it compiles with
    its kernels and moves nothing between chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.bfv import BFVContext
    from repro.core.params import make_params
    p = make_params(n=1024, t=65537, k=4)
    ctx = BFVContext(p, backend="pallas", interpret=False)
    ctx.mesh = Mesh(np.array(v5e_2x2.devices), ("data",))
    rep, lanes = (NamedSharding(ctx.mesh, P()), NamedSharding(ctx.mesh, P("data")))
    shape = lambda a, sh=rep: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    lq, lp = jax.tree.map(shape, ctx.limb_q), jax.tree.map(shape, ctx.limb_p)
    ksk = jax.ShapeDtypeStruct((p.k, p.k, p.n), jnp.int64, sharding=rep)
    for B in (4, 8):                        # one and two lanes per chip
        batch = jax.ShapeDtypeStruct((B, 2, p.k, p.n), jnp.int64, sharding=lanes)
        fn = ctx._lane_program(ctx._mul_j, 4, (True, True), True)
        text = fn.lower(lq, lp, ksk, ksk, batch, batch).compile().as_text()
        assert "tpu_custom_call" in text
        for op in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
            assert op not in text, (B, op)


def test_dot_on_data_mesh_has_no_collective(v5e_2x2):
    """On a 4-chip data mesh the inner product runs under shard_map
    (`dot_scalars` through `_lane_map_mesh`): its kernel compiles, named
    under `he.dot`, and nothing moves between chips."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.bfv import BFVContext
    from repro.core.params import make_params
    p = make_params(n=1024, t=65537, k=4)
    ctx = BFVContext(p, backend="pallas", interpret=False)
    ctx.mesh = Mesh(np.array(v5e_2x2.devices), ("data",))
    rep, lanes = (NamedSharding(ctx.mesh, P()), NamedSharding(ctx.mesh, P("data")))
    lq = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
                      ctx.limb_q)
    cs = jax.ShapeDtypeStruct((ctx.DOT_TERMS,), jnp.int64, sharding=rep)
    batch = jax.ShapeDtypeStruct((4, 2, p.k, p.n), jnp.int64, sharding=lanes)
    nb = ctx.DOT_TERMS + 1
    fn = ctx._lane_program(ctx._dot_j, 1, (False,) + (True,) * nb, True)
    text = fn.lower(lq, cs, *[batch] * nb).compile().as_text()
    names = [m.group(1) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             for m in [re.search(r'op_name="([^"]*)"', line)] if m]
    assert names and all("he.dot/" in n for n in names), names
    for op in ("all-gather", "all-reduce", "collective-permute", "all-to-all"):
        assert op not in text, op


def test_keyswitch_ntt_is_named_in_the_rotation(one_chip):
    """The rotation's key-switch NTT kernel carries its `jax.named_scope`
    names in the compiled program's op_name, where a trace reads them."""
    import re
    from repro.core.bfv import BFVContext
    from repro.core.params import make_params
    p = make_params(n=1024, t=65537, k=4)
    ctx = BFVContext(p, backend="pallas", interpret=False)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    lq = jax.tree.map(shape, ctx.limb_q)
    ksk = jax.ShapeDtypeStruct((p.k, p.k, p.n), jnp.int64, sharding=one_chip)
    src, sign = jax.tree.map(shape, ctx._galois_tabs[p.rowswap_g])
    data = jax.ShapeDtypeStruct((2, p.k, p.n), jnp.int64, sharding=one_chip)
    text = ctx._rotate_j.lower(lq, ksk, ksk, src, sign, data).compile().as_text()
    names = [m.group(1) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             for m in [re.search(r'op_name="([^"]*)"', line)] if m]
    assert any("he.keyswitch/he.ntt/" in n for n in names), names
    assert any("he.keyswitch/he.intt/" in n for n in names), names


def test_stage_twiddles_match_reference_order():
    """Host-side table expansion: every stage entry is the twiddle the
    reference butterfly (core/ntt.py) applies to that flat index."""
    from repro.core.params import make_params
    from repro.kernels.ntt.ntt import stage_twiddles
    p = make_params(n=256, t=7681, k=2)
    n, log_n = p.n, p.log_n
    w, _ = stage_twiddles(p.Q.psi_rev, p.Q.q, inverse=False)
    wi, _ = stage_twiddles(p.Q.ipsi_rev, p.Q.q, inverse=True)
    flat, flat_i = w.reshape(2, log_n, n), wi.reshape(2, log_n, n)
    for s in range(log_n):
        m, t_len = 1 << s, n >> (s + 1)
        for i in range(n):
            assert flat[0, s, i] == p.Q.psi_rev[0, m + i // (2 * t_len)]
            h = n >> (s + 1)
            assert flat_i[1, s, i] == p.Q.ipsi_rev[1, h + i // (2 << s)]
    assert np.all(w < np.asarray(p.Q.q)[:, None, None, None])
