"""Token headers and claims per configuration profile.

Times are fixed, not read from the clock, so one seed gives the same
token bytes on any day: issued 2026-01-01T00:00:00Z, valid ten years.

- ``default_claims``: ``cap_tpu.testing.default_claims`` with the
  unique ``sub`` and ``jti`` of ``sign_unique_jwts``.
- ``rfc9068_at``: an OAuth 2.0 access token in the JWT profile of
  RFC 9068: header ``typ`` ``at+jwt`` (section 2.1); the required
  claims ``iss exp aud sub client_id iat jti`` (section 2.2), plus
  ``scope`` (section 2.2.3).
"""

from __future__ import annotations

IAT = 1767225600
TTL = 10 * 365 * 86400
PROFILES = ("default_claims", "rfc9068_at")


def make(profile: str, seed: int, j: int, kid: str, alg: str):
    """(header, claims) of pool token ``j``."""
    jti = f"tok-{seed % 10 ** 9:09d}-{j:09d}"
    if profile == "default_claims":
        # cap_tpu.testing.default_claims + sign_unique_jwts' sub/jti
        return ({"alg": alg, "typ": "JWT", "kid": kid},
                {"iss": "https://example.com/", "sub": f"user-{j:08d}",
                 "aud": ["client-id"], "iat": IAT, "nbf": IAT,
                 "exp": IAT + TTL, "jti": jti})
    if profile == "rfc9068_at":
        return ({"alg": alg, "typ": "at+jwt", "kid": kid},
                {"iss": "https://as.example.com/", "exp": IAT + TTL,
                 "aud": "https://rs.example.com/", "sub": f"user-{j:08d}",
                 "client_id": f"client-{j % 64:02d}", "iat": IAT,
                 "jti": jti, "scope": "openid profile read"})
    raise ValueError(f"unknown claims profile {profile!r}")
