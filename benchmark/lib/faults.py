"""Keysets put in the program's place to show that the comparison
fails what it must fail.

- ``ControlKeySet``: the reference with the signature check left out,
  the step a later PR could be tempted by ("trust the kid"). It breaks
  the configuration's guarantee that every forgery is rejected.
- ``Faulty``: wraps a sound keyset and breaks its answers where they
  are produced: one answer altered per batch, half of the batch left
  out (its answers copied from the other half), or the previous
  batch's answers returned unchanged.
"""

from __future__ import annotations

import json

from .fixtures import b64u_dec


class ControlRejected(Exception):
    """The control's reject."""


class ControlKeySet:
    """Accepts a token whose header names a known kid and its alg,
    without checking the signature."""

    def __init__(self, jwks_doc: dict):
        self._algs = {k["kid"]: k["alg"] for k in jwks_doc["keys"]}

    def _one(self, token: str):
        try:
            h, p, _ = token.split(".")
            header = json.loads(b64u_dec(h))
            if self._algs.get(header.get("kid")) != header.get("alg"):
                return ControlRejected("unknown kid or alg")
            claims = json.loads(b64u_dec(p))
        except ValueError:
            return ControlRejected("malformed")
        return claims if isinstance(claims, dict) else ControlRejected(
            "malformed")

    def verify_batch(self, tokens):
        return [self._one(t) for t in tokens]


FAULTS = ("alter_answer", "drop_half", "stale_state")


class Faulty:
    def __init__(self, inner, fault: str):
        if fault not in FAULTS:
            raise ValueError(fault)
        self._inner, self._fault, self._last = inner, fault, None

    def verify_batch(self, tokens):
        n = len(tokens)
        if self._fault == "drop_half" and n > 1:
            head = self._inner.verify_batch(tokens[: (n + 1) // 2])
            return [head[i % len(head)] for i in range(n)]
        out = self._inner.verify_batch(tokens)
        if self._fault == "alter_answer":
            for i, r in enumerate(out):
                if isinstance(r, dict):
                    out[i] = dict(r, sub="altered")
                    break
        elif self._fault == "stale_state":
            last, self._last = self._last, out
            if last is not None:
                return [last[i % len(last)] for i in range(n)]
        return out


# -- keysets a test puts in the program's place, by name, so that a
# worker process of a layout can be handed one (a lambda does not pickle)

def cpu_keyset(jwks_doc: dict, cfg: dict):
    """The program's CPU verifier over the same JWKS."""
    from cap_tpu.jwt.jwk import parse_jwks
    from cap_tpu.jwt.keyset import StaticKeySet

    return StaticKeySet([j.key for j in parse_jwks(jwks_doc)])


def faulty(fault: str, jwks_doc: dict, cfg: dict):
    """``cpu_keyset`` broken by ``fault`` (bind it with
    ``functools.partial``)."""
    return Faulty(cpu_keyset(jwks_doc, cfg), fault)
