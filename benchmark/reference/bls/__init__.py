"""Plain BLS12-381 reference: FastAggregateVerify and the wire encodings.

`fields`, `curve`, `hash_to_curve` and `pairing` are copies of the
pure-Python oracle the program ships (`consensus_specs_tpu/ops/bls/`), kept
here so that the benchmark's reference imports nothing of the program and
no later change to the program can move it.  This module holds the part of
the ciphersuite the benchmark needs, transcribed from the IETF BLS
signature draft's proof-of-possession scheme as the consensus specs use it.
"""

from __future__ import annotations

from .curve import (  # noqa: F401
    G1_GEN,
    g1,
    g1_from_bytes,
    g1_to_bytes,
    g2,
    g2_from_bytes,
    g2_to_bytes,
    subgroup_check_g1,
    subgroup_check_g2,
)
from .fields import R  # noqa: F401
from .hash_to_curve import DST_G2, hash_to_g2  # noqa: F401
from .pairing import pairing_check


def FastAggregateVerify(pubkeys: list[bytes], message: bytes,
                        signature: bytes) -> bool:
    """e(sum(PK), H(m)) == e(G1, S), with every point decompressed and
    subgroup-checked; any malformed input is False."""
    if len(pubkeys) == 0:
        return False
    try:
        sig = g2_from_bytes(signature)
        if not subgroup_check_g2(sig):
            return False
        agg = g1.infinity()
        for pk in pubkeys:
            p = g1_from_bytes(pk)
            if g1.is_inf(p) or not subgroup_check_g1(p):
                return False
            agg = g1.add(agg, p)
    except ValueError:
        return False
    return pairing_check([(agg, hash_to_g2(bytes(message), DST_G2)),
                          (g1.neg(G1_GEN), sig)])
