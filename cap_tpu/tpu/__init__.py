"""TPU verify engine.

The performance layer of cap_tpu: batched big-number and elliptic-curve
arithmetic as JAX programs XLA-compiled for TPU, plus the
batching/bucketing runtime that feeds it. Hand-written Pallas kernels
cover the EC/Ed and post-quantum hot loops and default ON for TPU
backends — the fused mixed-add (pallas_madd.py, CAP_TPU_PALLAS_MADD),
the fused REDC (pallas_redc.py, CAP_TPU_PALLAS), the fused 8-stage
NTT (pallas_ntt.py, CAP_TPU_PALLAS_NTT), and the Keccak-f[1600] lane
kernel (pallas_keccak.py, CAP_TPU_PALLAS_KECCAK); A/Bs in
docs/PERF.md, CPU keeps the XLA path as the parity reference. The reference has no
native/accelerated components
(SURVEY.md §2) — this subsystem is the new framework's replacement for
the Go stdlib crypto inner loops (crypto/rsa, crypto/ecdsa,
crypto/ed25519).

Layout convention: big integers are little-endian base-2^16 limb vectors
stored **limb-first**: an array of shape [K, N] holds N numbers of K
limbs, so the batch axis N rides the TPU's 128-wide vector lanes and
limb shifts are cheap sublane moves.
"""


def program_name(name: str):
    """Decorator: give a function the stable name ``name``. JAX names a
    jitted program after its function (the HLO module ``jit_<name>``,
    its profile events, its compile events), so a program named by a
    module constant keeps that name through any refactor."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return rename
