"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel directory ships three files:
  <name>.py   the pl.pallas_call kernel with explicit BlockSpec VMEM tiling
  ops.py      the public wrapper
  ref.py      the pure-jnp oracle the tests assert against

Hardware adaptation (see DESIGN.md §3): Pallas TPU has no 64-bit integer
ALU, so all modular arithmetic uses uint32 lanes with 16-bit limb
splitting — Shoup multiplication for known twiddles (NTT) and Barrett
reduction for ciphertext-ciphertext products (modops).  The MXU is
float-only; the NTT stays on the VPU with exact integer ops.

Kernels:
  ntt            negacyclic NTT, whole polynomial VMEM-resident, radix-2
                 stages in-kernel, grid over (batch x limb)
  modops         dyadic (pointwise) ciphertext ops: Barrett modmul/add/sub,
                 and the plaintext-scalar inner product of the LT circuit
  rotate_reduce  log-depth packed aggregation (the paper's rotate+add sum)
  flash_attn     blocked online-softmax attention for the LM substrate
                 (causal / local-window / logit-softcap variants)

Batched evaluation path
-----------------------
The BFV core consumes the NTT and modops kernels through
`core/limbops.LimbOps`, a dispatch layer that accepts (..., k, n)
arrays — a whole column of ciphertext blocks at once — and flattens the
batch into the kernels' (rows, n) layout: the NTT grid indexes each
limb's twiddle tables by grid position, and the pointwise kernels take a
per-row modulus column.  The `backend` flag on `BFVContext` /
`BFVBackend(kernel_backend=...)` selects "pallas" vs the "ref" jnp
oracles ("auto" picks Pallas on TPU).  Every kernel takes `interpret`
from the platform unless told otherwise (`resolve_interpret`): compiled
on a TPU, the Pallas interpreter elsewhere.  Both
paths are exact and bit-identical, verified by tests/test_limbops_parity
and tests/test_batched_equivalence.  `MockBackend(kernel_reduce=True)`
likewise routes its `sum_slots` data movement through the rotate_reduce
kernel while charging the looped schedule's op counts.
"""
import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> Pallas interpret mode exactly when not running on a TPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
