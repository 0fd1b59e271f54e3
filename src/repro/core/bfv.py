"""RNS-BFV scheme (HPS multiplication variant), JAX-native.

Layout conventions
------------------
* polynomial:  (k, n) int64, limb-major, coefficients in [0, q_i)
* ciphertext:  (2, k, n) — (c0, c1), coefficient domain
* block batch: (nblocks, 2, k, n) — a whole column of ciphertext blocks
               stacked on a leading axis (`CiphertextBatch`)
* keys:        stored in NTT (evaluation) domain
* key switch:  per-limb RNS gadget (digit i = centered residue mod q_i);
               the gadget matrix g_i mod q_j is exactly the identity, so
               the "encrypt g_i * s'" term touches only limb i.

Batched evaluation path
-----------------------
Every op accepts one ciphertext or a stacked column of blocks.  The
limb-level hot loops — pointwise RNS mul/add/sub and the forward/inverse
NTT — are routed through `core/limbops.LimbOps`, which dispatches to the
Pallas kernels (`kernels/modops`, `kernels/ntt`) or to the pure-jnp
`*_ref` oracles depending on the `backend` flag passed to `BFVContext`
(default: the NSHEDB_LIMB_BACKEND env var, "auto" = Pallas on TPU, ref
elsewhere; kernels run compiled on a TPU and in interpret mode
elsewhere).  Both paths produce bit-identical residues, so decryption
results do not depend on the dispatch choice.

The kernel-bearing programs — multiply, plaintext multiply, rotation,
encryption, decryption — are compiled for one ciphertext and mapped
over block lanes (`_lane_map`).  On one device the host runs the
program once per lane, so it compiles once whatever the batch size.
On a data mesh (engine/sharded.py places the tables there with
`place_tables`) the program runs under `jax.shard_map`, each device
mapping it over its own lanes: XLA cannot partition a Mosaic call, but
a shard_map body is already per device.  The programs take both
`LimbOps` (pytrees of the bases' tables) and the keys as arguments, so
no table is embedded in a compiled program.  Elementwise ops stay
batched (`_elementwise_j`).

All deterministic arithmetic is jitted; sampling happens host-side with a
seeded numpy Generator so tests are reproducible.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .limbops import LimbLocalOps, LimbOps
from .mathutil import centered, crt_reconstruct
from .noise import NoiseModel
from .params import HEParams


@dataclasses.dataclass
class Ciphertext:
    data: jnp.ndarray        # (2, k, n) int64, coefficient domain
    noise: float             # analytic log2 |invariant noise|
    params: HEParams

    @property
    def budget(self) -> float:
        return -(self.noise + 1.0)


@dataclasses.dataclass
class CiphertextBatch:
    """A stacked column of ciphertext blocks with one shared op history.

    data is (nblocks, 2, k, n).  Blocks of an encrypted column go through
    identical circuits, so a single analytic noise scalar — the max over
    the stacked blocks — serves the whole batch.  When block noises do
    differ (e.g. after a validity multiply on the last block), `noise`
    is a per-block numpy vector of length `nblocks` instead, which lets
    `_maybe_refresh`/`ensure_levels` refresh only the exhausted lanes
    rather than paying a conservative-max penalty for the whole batch.

    `live` supports sharded execution (engine/sharded.py): when the lane
    count is padded up to a multiple of the shard count with zero
    blocks, `live` records the logical block count.  `nblocks` reports
    the live count (so OpStats/noise accounting stay byte-identical to
    the unpadded path) while `nphys` reports the padded leading axis.
    """
    data: jnp.ndarray        # (nblocks, 2, k, n) int64
    noise: "float | np.ndarray"
    params: HEParams
    live: int | None = None

    @property
    def nblocks(self) -> int:
        return self.live if self.live is not None else self.data.shape[0]

    @property
    def nphys(self) -> int:
        return self.data.shape[0]

    @property
    def budget(self) -> float:
        return float(-(np.max(self.noise) + 1.0))


@dataclasses.dataclass
class SecretKey:
    s: np.ndarray            # (n,) ternary
    s_ntt: jnp.ndarray       # (k, n)


@dataclasses.dataclass
class PublicKey:
    b_ntt: jnp.ndarray       # (k, n)
    a_ntt: jnp.ndarray       # (k, n)


@dataclasses.dataclass
class KSwitchKey:
    b: jnp.ndarray           # (k, k, n) NTT domain, digit-major
    a: jnp.ndarray           # (k, k, n)


@dataclasses.dataclass
class Keys:
    sk: SecretKey
    pk: PublicKey
    rlk: KSwitchKey
    gks: dict[int, KSwitchKey]   # galois element -> key


@functools.partial(jax.jit, static_argnames=("mesh", "data_sharded"))
def _ksw_gathered(poly, kb, ka, q, psi, ipsi, ninv, *, mesh, data_sharded):
    """shard_map key-switch on a ("data", "model") mesh (see
    BFVContext.kswitch_gathered for the math).  poly is (B, k, n); the
    key is sharded on its *output*-limb axis 1, the tables on their limb
    axis, and the batch on "data" when B divides the data axis (a
    replicated batch — singletons, odd sizes — uses a None spec; the
    digit gather over "model" is the only hand-placed collective either
    way)."""
    P = jax.sharding.PartitionSpec
    dspec = "data" if data_sharded else None

    def body(p, kbl, kal, ql, psil, ipsil, ninvl):
        half = ql // 2
        cent = p - ql[:, None] * (p > half[:, None])                # (B, kL, n)
        gath = jax.lax.all_gather(cent, "model", axis=1, tiled=True)  # (B, k, n)
        digits = gath[:, :, None, :] % ql[None, None, :, None]      # (B, k, kL, n)
        ops = LimbLocalOps(ql, psil, ipsil, ninvl)
        d_ntt = ops.ntt(digits)
        acc_b = jnp.sum(ops.mul(d_ntt, kbl[None]), axis=1) % ql[:, None]
        acc_a = jnp.sum(ops.mul(d_ntt, kal[None]), axis=1) % ql[:, None]
        return ops.intt(acc_b), ops.intt(acc_a)

    specs = (P(dspec, "model", None), P(None, "model", None),
             P(None, "model", None), P("model"), P("model", None),
             P("model", None), P("model"))
    with jax.named_scope("he.keyswitch"):
        return jax.shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=(P(dspec, "model", None),
                                        P(dspec, "model", None)))(
            poly, kb, ka, q, psi, ipsi, ninv)


class BFVContext:
    """Binds a parameter set; owns jitted primitives and key material ops.

    `backend` / `interpret` select the limb-level execution path (see
    module docstring); all ciphertext ops accept `Ciphertext` and
    `CiphertextBatch` interchangeably and preserve the input type.
    `stats` is the object whose `dispatches` field counts the jitted
    programs the host calls (the engine passes its OpStats).

    Device programs carry `jax.named_scope` names, which reach each HLO
    instruction's `op_name`: he.keyswitch, he.tensor, he.hps, he.galois,
    he.dot, he.encrypt, he.decrypt, and he.ntt / he.intt (LimbOps).
    """

    def __init__(self, params: HEParams, seed: int = 0,
                 backend: str | None = None, interpret: bool | None = None,
                 stats=None):
        self.params = params
        self.stats = stats if stats is not None else types.SimpleNamespace(
            dispatches=0)
        self.noise_model = NoiseModel(params)
        self.rng = np.random.default_rng(seed)
        p = params
        self.limb_q = LimbOps(p.Q, backend=backend, interpret=interpret)
        self.limb_p = LimbOps(p.P, backend=backend, interpret=interpret)
        self.qQ = jnp.asarray(p.Q.q)
        self.qP = jnp.asarray(p.P.q)
        self.delta = jnp.asarray(p.delta_mod_q)          # (k,)
        self.qinv_p = jnp.asarray(p.q_inv_mod_p)         # (kp,)
        cqp, cpq = p.conv_q_to_p, p.conv_p_to_q
        self.c_qp = tuple(jnp.asarray(x) for x in
                          (cqp.a_hat_inv_mod_a, cqp.a_hat_mod_b, cqp.a_mod_b, cqp.a_inv))
        self.c_pq = tuple(jnp.asarray(x) for x in
                          (cpq.a_hat_inv_mod_a, cpq.a_hat_mod_b, cpq.a_mod_b, cpq.a_inv))
        self._galois_tabs = {
            g: (jnp.asarray(tab.src), jnp.asarray(tab.sign)) for g, tab in p.galois.items()
        }
        # jitted primitives; each takes the LimbOps it uses as arguments.
        # The kernel-bearing ones are compiled for one ciphertext and
        # mapped over block lanes (_lane_map).
        self._ntt_j = jax.jit(LimbOps.ntt)
        self._encrypt_j = jax.jit(self._encrypt_impl)
        self._decrypt_j = jax.jit(self._decrypt_impl)
        self._mul_j = jax.jit(self._mul_impl)
        self._mul_tensor_j = jax.jit(self._mul_tensor_impl)
        self._mul_plain_j = jax.jit(self._mul_plain_impl)
        self._rotate_j = jax.jit(self._rotate_impl)
        self._apply_galois_j = jax.jit(self._apply_galois_impl)
        self._elementwise_j = jax.jit(self._elementwise_impl,
                                      static_argnames="op")
        self._dot_j = jax.jit(self._dot_impl)
        self.mesh = None        # data mesh the tables are placed on
        self._spmd: dict = {}   # _lane_program cache

    def _ntt_q(self, x):
        """Forward NTT over base Q (keygen and benchmarks)."""
        return self._lane_map(self._ntt_j, (self.limb_q,), (x,), (False,))

    def place_tables(self, mesh) -> None:
        """Replicate both bases' tables and the Galois gathers over a
        1-D ("data",) mesh, on which `_lane_map` then runs; None returns
        them to uncommitted one-device arrays."""
        if mesh is None:
            move = lambda a: jnp.asarray(np.asarray(a))
        else:
            rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            move = lambda a: jax.device_put(a, rep)
        self.limb_q = jax.tree.map(move, self.limb_q)
        self.limb_p = jax.tree.map(move, self.limb_p)
        self._galois_tabs = jax.tree.map(move, self._galois_tabs)
        self.mesh = mesh

    def _dispatch(self, prog, *args, **kwargs):
        """Call one jitted program: one host dispatch."""
        self.stats.dispatches += 1
        return prog(*args, **kwargs)

    def _lane_map(self, prog, shared, operands, batched):
        """prog(*shared, *operands) with a one-ciphertext program:
        operands[i] is a (B, ...) batch where batched[i], else one value
        every lane shares; results are stacked like the batch."""
        if self.mesh is not None:
            return self._lane_map_mesh(prog, shared, operands, batched)
        if not any(batched):
            return self._dispatch(prog, *shared, *operands)
        B = next(x for x, b in zip(operands, batched) if b).shape[0]
        return jnp.stack([
            self._dispatch(prog, *shared, *(x[i] if b else x
                                            for x, b in zip(operands, batched)))
            for i in range(B)])

    def _lane_map_mesh(self, prog, shared, operands, batched):
        """`_lane_map` on the data mesh: batches split by lane over
        "data" (replicated when B does not divide it), shared values
        replicated; each device maps prog over its own lanes."""
        P = jax.sharding.PartitionSpec
        mesh, batched = self.mesh, tuple(batched)
        split = any(batched) and all(x.shape[0] % mesh.shape["data"] == 0
                                     for x, b in zip(operands, batched) if b)
        lane = P("data") if split else P()
        operands = [jax.device_put(x, jax.sharding.NamedSharding(
            mesh, lane if b else P())) for x, b in zip(operands, batched)]
        return self._dispatch(self._lane_program(prog, len(shared), batched, split),
                              *shared, *operands)

    def _lane_program(self, prog, nshared: int, batched: tuple, split: bool):
        """The jitted shard_map program `_lane_map_mesh` runs (cached)."""
        P = jax.sharding.PartitionSpec
        key = (prog, self.mesh, nshared, batched, split)
        if key not in self._spmd:
            lane = P("data") if split else P()

            def body(*args):
                sh, ops = args[:nshared], args[nshared:]

                def one(lanes):
                    it = iter(lanes)
                    return prog(*sh, *(next(it) if b else x
                                       for x, b in zip(ops, batched)))
                lanes = [x for x, b in zip(ops, batched) if b]
                if not lanes:
                    return prog(*sh, *ops)
                if lanes[0].shape[0] == 1:   # lax.map would add ~0.3 GB temp
                    return one([x[0] for x in lanes])[None]
                return jax.lax.map(one, lanes)

            self._spmd[key] = jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(),) * nshared + tuple(lane if b else P() for b in batched),
                out_specs=lane if any(batched) else P(), check_vma=False))
        return self._spmd[key]

    # --------------------------------------------------------- type glue
    @staticmethod
    def _like(ref, data, noise):
        """Result wrapper preserving Ciphertext vs CiphertextBatch type."""
        return dataclasses.replace(ref, data=data, noise=noise)

    @staticmethod
    def _pick(a, b):
        """Of two operands, the one whose type the result should take
        (the batched one, when single and batch are mixed)."""
        return a if a.data.ndim >= b.data.ndim else b

    @staticmethod
    def pack_noises(noises: list) -> "float | np.ndarray":
        """Scalar when uniform (the common case), else a per-block vector."""
        vals = [float(v) for v in noises]
        if all(v == vals[0] for v in vals):
            return vals[0]
        return np.asarray(vals, dtype=np.float64)

    def stack_cts(self, cts: list) -> CiphertextBatch:
        """Stack single-block ciphertexts into one batch (pure layout)."""
        assert cts and all(isinstance(c, Ciphertext) for c in cts)
        return CiphertextBatch(jnp.stack([c.data for c in cts]),
                               self.pack_noises([c.noise for c in cts]),
                               self.params)

    def unstack_cts(self, batch: CiphertextBatch) -> list:
        per = batch.noise if np.ndim(batch.noise) else None
        return [Ciphertext(batch.data[i],
                           float(per[i]) if per is not None else batch.noise,
                           self.params)
                for i in range(batch.nblocks)]

    # ------------------------------------------------------------- sampling
    def _sample_uniform_ntt(self) -> jnp.ndarray:
        p = self.params
        cols = [self.rng.integers(0, q, p.n, dtype=np.int64) for q in p.Q.primes]
        return jnp.asarray(np.stack(cols))

    def _sample_ternary(self) -> np.ndarray:
        return self.rng.integers(-1, 2, self.params.n).astype(np.int64)

    def _sample_err(self) -> np.ndarray:
        e = np.rint(self.rng.normal(0.0, self.params.err_std, self.params.n))
        bound = math.ceil(6 * self.params.err_std)
        return np.clip(e, -bound, bound).astype(np.int64)

    def _reduce_small(self, poly: np.ndarray) -> jnp.ndarray:
        """(n,) small centered ints -> (k, n) residues."""
        return jnp.asarray(poly[None, :] % np.asarray(self.params.Q.primes)[:, None])

    # -------------------------------------------------------------- keygen
    def keygen(self, galois_steps: tuple[int, ...] | None = None) -> Keys:
        p = self.params
        s = self._sample_ternary()
        s_ntt = self._ntt_q(self._reduce_small(s))
        a_ntt = self._sample_uniform_ntt()
        e_ntt = self._ntt_q(self._reduce_small(self._sample_err()))
        b_ntt = (-(a_ntt * s_ntt % self.qQ[:, None]) - e_ntt) % self.qQ[:, None]
        pk = PublicKey(b_ntt=b_ntt, a_ntt=a_ntt)
        sk = SecretKey(s=s, s_ntt=s_ntt)

        s2_ntt = (s_ntt * s_ntt) % self.qQ[:, None]
        rlk = self._make_kswitch_key(s_ntt, s2_ntt)

        gks: dict[int, KSwitchKey] = {}
        steps = galois_steps if galois_steps is not None else tuple(p.rot_gs)
        gs = [p.rot_gs[st] for st in steps] + [p.rowswap_g]
        for g in gs:
            src, sign = self._galois_tabs[g]
            s_rot = np.asarray((sign * jnp.asarray(s)[src]))
            s_rot_ntt = self._ntt_q(self._reduce_small(s_rot))
            gks[g] = self._make_kswitch_key(s_ntt, s_rot_ntt)
        return Keys(sk=sk, pk=pk, rlk=rlk, gks=gks)

    def _make_kswitch_key(self, s_ntt: jnp.ndarray, target_ntt: jnp.ndarray) -> KSwitchKey:
        """KSK encrypting gadget(target): digit i carries target on limb i only."""
        p = self.params
        k = p.k
        bs, as_ = [], []
        for i in range(k):
            a_i = self._sample_uniform_ntt()
            e_i = self._ntt_q(self._reduce_small(self._sample_err()))
            b_i = (-(a_i * s_ntt % self.qQ[:, None]) - e_i) % self.qQ[:, None]
            b_i = b_i.at[i].set((b_i[i] + target_ntt[i]) % self.qQ[i])
            bs.append(b_i)
            as_.append(a_i)
        return KSwitchKey(b=jnp.stack(bs), a=jnp.stack(as_))

    # ------------------------------------------------------------- encrypt
    def encrypt(self, m_poly: jnp.ndarray, pk: PublicKey) -> Ciphertext:
        """m_poly: (n,) int64 mod t (use BatchEncoder to build it)."""
        u = self._reduce_small(self._sample_ternary())
        e0 = self._reduce_small(self._sample_err())
        e1 = self._reduce_small(self._sample_err())
        data = self._lane_map(
            self._encrypt_j, (self.limb_q,),
            (jnp.asarray(m_poly), u, e0, e1, pk.b_ntt, pk.a_ntt), (False,) * 6)
        return Ciphertext(data=data, noise=self.noise_model.fresh(), params=self.params)

    @jax.named_scope("he.encrypt")
    def _encrypt_impl(self, lq, m, u, e0, e1, pkb, pka):
        q = self.qQ[:, None]
        u_ntt = lq.ntt(u)
        c0 = (lq.intt(lq.mul(pkb, u_ntt)) + e0 + self.delta[:, None] * m[None, :]) % q
        c1 = (lq.intt(lq.mul(pka, u_ntt)) + e1) % q
        return jnp.stack([c0, c1])

    def encrypt_zero(self, pk: PublicKey) -> Ciphertext:
        return self.encrypt(jnp.zeros(self.params.n, dtype=jnp.int64), pk)

    # ------------------------------------------------------------- decrypt
    def decrypt(self, ct, sk: SecretKey) -> jnp.ndarray:
        """Decrypt a Ciphertext -> (n,) or a CiphertextBatch -> (nb, n)."""
        return self._lane_map(self._decrypt_j, (self.limb_q,),
                              (ct.data, sk.s_ntt), (ct.data.ndim == 4, False))

    @jax.named_scope("he.decrypt")
    def _decrypt_impl(self, lq, data, s_ntt):
        p = self.params
        q = self.qQ[:, None]
        c0, c1 = data[..., 0, :, :], data[..., 1, :, :]
        x = lq.reduce(c0 + lq.intt(lq.mul(lq.ntt(c1), s_ntt)))
        hat_inv, _, _, q_inv_f = self.c_qp
        y = lq.reduce(x * hat_inv[:, None])
        yt = y * p.t
        int_part = jnp.sum(yt // q, axis=-2)
        frac = jnp.sum((yt % q).astype(jnp.float64) * q_inv_f[:, None], axis=-2)
        return (int_part + jnp.round(frac).astype(jnp.int64)) % p.t

    # ------------------------------------------------------- add/sub/neg
    def _elementwise_impl(self, lq, x, y, op: str):
        """The residue arithmetic of the cheap ops, one fused program per
        (op, shape).  Ciphertext residues are < q < 2^30; plaintext
        coefficients and scalars are < t < 2^17."""
        q = self.qQ[:, None]
        if op == "add":
            return lq.reduce(x + y)
        if op == "sub":
            return lq.reduce(x - y + q)
        if op == "neg":
            return lq.reduce(q - x)
        if op == "mul_scalar":                       # y: scalar c < t
            return lq.reduce(x * y)
        if op == "add_plain":                        # y: (n,) poly, or scalar c
            d = self.delta[:, None] * y if jnp.ndim(y) else (
                jnp.zeros_like(x[..., 0, :, :]).at[..., 0].set(self.delta * y))
            c0 = lq.reduce(x[..., 0, :, :] + lq.reduce(d))
            return x.at[..., 0, :, :].set(c0)
        raise ValueError(op)

    def _elementwise(self, op: str, x, y):
        return self._dispatch(self._elementwise_j, self.limb_q, x, y, op=op)

    DOT_TERMS = 32      # terms per inner-product program (one compile)

    @jax.named_scope("he.dot")
    def _dot_impl(self, lq, cs, acc, *datas):
        return lq.dot(acc, datas, cs)

    def dot_scalars(self, datas: list, coeffs: list):
        """sum_i coeffs[i] * datas[i] over residues, coefficients in [0, t)
        (< 2^17), as a few programs of DOT_TERMS terms each (the last
        padded with zero coefficients), so every length shares one
        compilation.  A program runs on any block batch; on a data mesh
        it runs under shard_map (`_lane_map_mesh`), since XLA cannot
        partition the kernel."""
        if self.limb_q.backend == "pallas" and self.params.t > 1 << 17:
            raise ValueError(f"the inner-product kernel takes coefficients "
                             f"below 2^17; t = {self.params.t}")
        g = self.DOT_TERMS
        acc = jnp.zeros_like(datas[0])
        coeffs = list(coeffs)
        for i in range(0, len(datas), g):
            part = datas[i:i + g]
            pad = g - len(part)
            cs = jnp.asarray(coeffs[i:i + g] + [0] * pad, dtype=jnp.int64)
            args = (cs, acc, *part, *[part[0]] * pad)
            if self.mesh is None:
                acc = self._dispatch(self._dot_j, self.limb_q, *args)
            else:
                acc = self._lane_map_mesh(self._dot_j, (self.limb_q,), args,
                                          [False] + [x.ndim == 4 for x in args[1:]])
        return acc

    def add(self, a, b):
        out = self._pick(a, b)
        return self._like(out, self._elementwise("add", a.data, b.data),
                          self.noise_model.add(a.noise, b.noise))

    def sub(self, a, b):
        out = self._pick(a, b)
        return self._like(out, self._elementwise("sub", a.data, b.data),
                          self.noise_model.add(a.noise, b.noise))

    def neg(self, a):
        return self._like(a, self._elementwise("neg", a.data, 0), a.noise)

    def add_plain(self, a, m_poly: jnp.ndarray):
        data = self._elementwise("add_plain", a.data, jnp.asarray(m_poly))
        return self._like(a, data, self.noise_model.add(a.noise, a.noise))

    def sub_from_plain(self, m_poly: jnp.ndarray, a):
        """Encrypted (m - a)."""
        return self.add_plain(self.neg(a), m_poly)

    # ------------------------------------------------------ plain multiply
    def mul_plain(self, a, m_poly: jnp.ndarray):
        m = jnp.asarray(m_poly)
        batched = a.data.ndim == 4
        data = self._lane_map(self._mul_plain_j, (self.limb_q,), (a.data, m),
                              (batched, batched and m.ndim == 2))
        return self._like(a, data, self.noise_model.mul_plain(a.noise))

    # ------------------------------------------------------ scalar constants
    def mul_scalar(self, a, c: int):
        """Multiply by the constant polynomial c — no NTT, tight noise growth."""
        c %= self.params.t
        data = self._elementwise("mul_scalar", a.data, c)
        return self._like(a, data, self.noise_model.mul_scalar(a.noise, c))

    def add_scalar(self, a, c: int):
        """Add the constant c to every slot.

        The batch encoding of the all-c vector is the constant polynomial c,
        so only coefficient 0 of c0 moves (by delta*c per limb)."""
        c %= self.params.t
        data = self._elementwise("add_plain", a.data, c)
        return self._like(a, data, self.noise_model.add(a.noise, a.noise))

    def sub_from_scalar(self, c: int, a):
        """Encrypted (c - a) for scalar c."""
        return self.add_scalar(self.neg(a), c)

    def _mul_plain_impl(self, lq, data, m):
        """One ciphertext (2, k, n) times one plaintext polynomial (n,)
        (coefficients < t < q, so already residues in every limb)."""
        m_ntt = lq.ntt(jnp.broadcast_to(m, (lq.k, m.shape[-1])))
        out0 = lq.intt(lq.mul(lq.ntt(data[..., 0, :, :]), m_ntt))
        out1 = lq.intt(lq.mul(lq.ntt(data[..., 1, :, :]), m_ntt))
        return jnp.stack([out0, out1], axis=-3)

    # ------------------------------------------------- HPS base conversion
    @staticmethod
    @jax.named_scope("he.hps")
    def _fbc(x, conv, lin: LimbOps, lout: LimbOps):
        """Exact fast base conversion of the centered value of x.

        x: (..., ka, n) residues of base `lin`; conv: jnp'ed BaseConv
        tuple; returns (..., kb, n) residues of base `lout`.  Every
        product is < 2^60 and every sum < 2^36 before its reduction.
        """
        hat_inv, hat_mod_b, a_mod_b, a_inv = conv
        y = lin.reduce(x * hat_inv[:, None])
        v = jnp.round(jnp.sum(y.astype(jnp.float64) * a_inv[:, None], axis=-2)).astype(jnp.int64)
        terms = lout.reduce(y[..., :, None, :] * hat_mod_b[:, :, None])
        acc = jnp.sum(terms, axis=-3)                      # (..., kb, n) < ka * b_j
        ka = hat_inv.shape[0]                              # v <= ka: shift by ka*b_j
        return lout.reduce(acc - v[..., None, :] * a_mod_b[:, None]
                           + ka * lout.q[:, None])

    # ------------------------------------------------------- ct-ct multiply
    def mul(self, a, b, rlk: KSwitchKey, mesh=None):
        """HPS tensor + relinearization.  With a 2-D query mesh the
        relin key-switch all-gathers its decomposition digits over the
        mesh "model" axis (engine/sharded.py) — byte-identical output,
        different collective structure."""
        if mesh is None:
            data = self._lane_map(
                self._mul_j, (self.limb_q, self.limb_p, rlk.b, rlk.a),
                (a.data, b.data), (a.data.ndim == 4, b.data.ndim == 4))
        else:
            r0, r1, r2 = self._dispatch(self._mul_tensor_j, self.limb_q,
                                        self.limb_p, a.data, b.data)
            ks0, ks1 = self.kswitch_gathered(r2, rlk, mesh)
            q = self.qQ[:, None]
            data = jnp.stack([(r0 + ks0) % q, (r1 + ks1) % q], axis=-3)
        nz = self.noise_model
        return self._like(self._pick(a, b), data,
                          nz.keyswitch(nz.mul(a.noise, b.noise)))

    @jax.named_scope("he.tensor")
    def _mul_tensor_impl(self, lq, lp, da, db):
        """Steps 1-4 of the HPS multiply: the degree-2 tensor scaled back
        to base Q, before relinearization."""
        p = self.params
        qQ, qP = self.qQ, self.qP
        a0, a1 = da[..., 0, :, :], da[..., 1, :, :]
        b0, b1 = db[..., 0, :, :], db[..., 1, :, :]
        # 1. lift to Q ∪ P
        aP = (self._fbc(a0, self.c_qp, lq, lp), self._fbc(a1, self.c_qp, lq, lp))
        bP = (self._fbc(b0, self.c_qp, lq, lp), self._fbc(b1, self.c_qp, lq, lp))
        # 2. NTT + tensor in both bases
        fa = [lq.ntt(a0), lq.ntt(a1)]
        fb = [lq.ntt(b0), lq.ntt(b1)]
        ga = [lp.ntt(aP[0]), lp.ntt(aP[1])]
        gb = [lp.ntt(bP[0]), lp.ntt(bP[1])]
        tq = [
            lq.intt(lq.mul(fa[0], fb[0])),
            lq.intt(lq.add(lq.mul(fa[0], fb[1]), lq.mul(fa[1], fb[0]))),
            lq.intt(lq.mul(fa[1], fb[1])),
        ]
        tp = [
            lp.intt(lp.mul(ga[0], gb[0])),
            lp.intt(lp.add(lp.mul(ga[0], gb[1]), lp.mul(ga[1], gb[0]))),
            lp.intt(lp.mul(gb[1], ga[1])),
        ]
        # 3. scale by t/Q exactly: r = (t*E - [tE]_Q) / Q, computed in base P
        rs = []
        for eq, ep in zip(tq, tp):
            rem_q = lq.reduce(eq * p.t)
            rem_p = self._fbc(rem_q, self.c_qp, lq, lp)
            r_p = lp.reduce(lp.reduce(ep * p.t - rem_p + qP[:, None])
                            * self.qinv_p[:, None])
            rs.append(self._fbc(r_p, self.c_pq, lp, lq))       # 4. back to base Q
        return rs[0], rs[1], rs[2]

    def _mul_impl(self, lq, lp, rlk_b, rlk_a, da, db):
        r0, r1, r2 = self._mul_tensor_impl(lq, lp, da, db)
        # 5. relinearize r2
        ks0, ks1 = self._kswitch_inner(lq, r2, rlk_b, rlk_a)
        return jnp.stack([lq.reduce(r0 + ks0), lq.reduce(r1 + ks1)], axis=-3)

    # --------------------------------------------------------- key switch
    @jax.named_scope("he.keyswitch")
    def _kswitch_inner(self, lq, poly, ksk_b, ksk_a):
        """Key-switch `poly` (coeff domain, (..., k, n)): coeff-domain pair."""
        q = self.qQ[:, None]
        qvec = self.qQ
        half = qvec // 2
        cent = poly - qvec[:, None] * (poly > half[:, None])       # centered digits
        # digit i mod q_j: cent_i + q_j is in (0, 2^31) for 30-bit primes
        digits = lq.reduce(cent[..., :, None, :] + q)              # (..., kd, k, n)
        d_ntt = lq.ntt(digits)
        acc_b = lq.reduce(jnp.sum(lq.mul(d_ntt, ksk_b), axis=-3))
        acc_a = lq.reduce(jnp.sum(lq.mul(d_ntt, ksk_a), axis=-3))
        return lq.intt(acc_b), lq.intt(acc_a)

    def kswitch_gathered(self, poly, ksk: KSwitchKey, mesh):
        """`_kswitch_inner` on a 2-D ("data", "model") mesh.

        Each device holds a (kL = k/M)-limb slice of `poly` and the
        output-limb slice of the key (KSwitchKey axis 1 is the output
        limb; axis 0, the digit, stays whole per device).  The centered
        digits — k*n int64 per block, the *minimal* cross-limb payload —
        all-gather along "model"; each device then reduces the gathered
        digits mod its local moduli, NTTs with its local tables,
        multiplies with its key slice, folds over the full digit axis
        and INTTs.  Same summation order, exact int64 throughout, so the
        output is byte-identical to the fused single-device path.
        """
        lead = poly.shape[:-2]
        B = math.prod(lead) if lead else 1
        p3 = poly.reshape((B,) + poly.shape[-2:])
        data_ax = mesh.shape.get("data", 1)
        data_sharded = B > 1 and B % data_ax == 0
        tabs = self.limb_q.arrays
        b, a = self._dispatch(_ksw_gathered, p3, ksk.b, ksk.a, self.qQ,
                              tabs["psi"], tabs["ipsi"], tabs["ninv"],
                              mesh=mesh, data_sharded=data_sharded)
        return b.reshape(poly.shape), a.reshape(poly.shape)

    # ------------------------------------------------------------ rotation
    @jax.named_scope("he.galois")
    def _apply_galois_impl(self, data, src, sign):
        return (sign * data[..., src]) % self.qQ[:, None]

    def _rotate_impl(self, lq, ksk_b, ksk_a, src, sign, data):
        """sigma_g (as its gather table) then key-switch back to s: the
        whole rotation, one program for every Galois element."""
        q = self.qQ[:, None]
        with jax.named_scope("he.galois"):
            rot = lq.reduce(sign * data[..., src] + q)
        ks0, ks1 = self._kswitch_inner(lq, rot[..., 1, :, :], ksk_b, ksk_a)
        return jnp.stack([lq.reduce(rot[..., 0, :, :] + ks0), ks1], axis=-3)

    def apply_galois(self, ct, g: int, gk: KSwitchKey, mesh=None):
        if mesh is None:
            src, sign = self._galois_tabs[g]
            data = self._lane_map(self._rotate_j,
                                  (self.limb_q, gk.b, gk.a, src, sign),
                                  (ct.data,), (ct.data.ndim == 4,))
        else:
            rot = self._dispatch(self._apply_galois_j, ct.data,
                                 *self._galois_tabs[g])
            ks0, ks1 = self.kswitch_gathered(rot[..., 1, :, :], gk, mesh)
            c0 = (rot[..., 0, :, :] + ks0) % self.qQ[:, None]
            data = jnp.stack([c0, ks1], axis=-3)
        return self._like(ct, data, self.noise_model.rotate(ct.noise))

    def rotate_rows(self, ct, step: int, gks: dict[int, KSwitchKey],
                    mesh=None):
        """Rotate both rows left by `step` (decomposed into power-of-two hops)."""
        p = self.params
        step %= p.row
        out = ct
        hop = 1
        while step:
            if step & 1:
                g = p.rot_gs[hop]
                out = self.apply_galois(out, g, gks[g], mesh=mesh)
            step >>= 1
            hop <<= 1
        return out

    def swap_rows(self, ct, gks: dict[int, KSwitchKey], mesh=None):
        g = self.params.rowswap_g
        return self.apply_galois(ct, g, gks[g], mesh=mesh)

    # --------------------------------------------------- slot-level helpers
    def sum_slots(self, ct, gks: dict[int, KSwitchKey]):
        """Rotate-and-add tree: every slot ends up holding the full sum.

        log2(n/2) row rotations + 1 row swap (paper §4.2.2 COUNT/SUM).
        """
        out = ct
        step = 1
        while step < self.params.row:
            out = self.add(out, self.rotate_rows(out, step, gks))
            step *= 2
        return self.add(out, self.swap_rows(out, gks))

    # ----------------------------------------------------- batched column API
    def add_many(self, a_cts: list, b_cts: list) -> list:
        """Blockwise a+b over two columns via one stacked call."""
        return self.unstack_cts(self.add(self.stack_cts(a_cts), self.stack_cts(b_cts)))

    def sub_many(self, a_cts: list, b_cts: list) -> list:
        return self.unstack_cts(self.sub(self.stack_cts(a_cts), self.stack_cts(b_cts)))

    def mul_plain_many(self, cts: list, m_poly: jnp.ndarray) -> list:
        """One plaintext polynomial against every block of a column."""
        return self.unstack_cts(self.mul_plain(self.stack_cts(cts), m_poly))

    def mul_many(self, a_cts: list, b_cts: list, rlk: KSwitchKey) -> list:
        """Blockwise ct-ct products (tensor + relin) in one stacked call."""
        return self.unstack_cts(self.mul(self.stack_cts(a_cts), self.stack_cts(b_cts), rlk))

    def rotate_rows_many(self, cts: list, step: int, gks: dict[int, KSwitchKey]) -> list:
        return self.unstack_cts(self.rotate_rows(self.stack_cts(cts), step, gks))

    def sum_slots_many(self, cts: list, gks: dict[int, KSwitchKey]) -> list:
        return self.unstack_cts(self.sum_slots(self.stack_cts(cts), gks))

    def fold_add(self, batch: CiphertextBatch) -> Ciphertext:
        """Sum a batch across its block axis into one ciphertext — the
        cross-block half of an aggregation.  Residues match the
        sequential add chain exactly (mod-q sums commute); the noise
        bound replays the same sequential `add` recurrence.  Only the
        `live` lanes participate: shard padding lanes may hold garbage
        after broadcasted single×batch ops and must never enter a sum."""
        nb = batch.nblocks
        data = jnp.sum(batch.data[:nb], axis=0) % self.qQ[:, None]
        per = batch.noise if np.ndim(batch.noise) else None
        noise = float(per[0]) if per is not None else batch.noise
        for i in range(1, nb):
            noise = self.noise_model.add(
                noise, float(per[i]) if per is not None else batch.noise)
        return Ciphertext(data, noise, self.params)

    # ------------------------------------------------------- noise measure
    def noise_budget_exact(self, ct: Ciphertext, sk: SecretKey) -> float:
        """Exact invariant-noise budget in bits (host-side bigint; tests)."""
        p = self.params
        q = self.qQ[:, None]
        lq = self.limb_q
        x = np.asarray((ct.data[0] + lq.intt(lq.mul(lq.ntt(ct.data[1]), sk.s_ntt))) % q)
        m = np.asarray(self.decrypt(ct, sk))
        Q = p.bigQ()
        tQ = p.t * Q
        worst = 1
        for j in range(p.n):
            X = crt_reconstruct([int(x[i, j]) for i in range(p.k)], list(p.Q.primes))
            w = centered((p.t * X - int(m[j]) * Q) % tQ, tQ)
            worst = max(worst, abs(w))
        return math.log2(Q) - 1.0 - math.log2(worst)
