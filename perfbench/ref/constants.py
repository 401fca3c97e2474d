"""BLS12-381 curve constants.

Parameters of the pairing-friendly curve family BLS12 instantiated at
x = -0xd201000000010000 (the "BLS12-381" curve used by Ethereum consensus).

Mirrors the parameter surface the reference consumes from the external `blst`
library (Lighthouse: crypto/bls/src/impls/blst.rs:9-15 and the
sizes at Lighthouse crypto/bls/src/lib.rs:38-48).

All values below are *validated at import time* against the BLS12 family
polynomial identities:

    r(x) = x^4 - x^2 + 1
    p(x) = (x - 1)^2 * r(x) / 3 + x

so a mis-remembered constant cannot slip through silently.
"""

# The BLS12 family parameter ("z" in the literature). Negative for BLS12-381.
X = -0xD201000000010000

# Base field modulus (381 bits).
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB

# Scalar field modulus (subgroup order, 255 bits).
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

# Curve equations: G1: y^2 = x^3 + 4 over Fp; G2: y^2 = x^3 + 4(u+1) over Fp2.
B_G1 = 4
B_G2 = (4, 4)  # 4 + 4u as (c0, c1)

# Cofactors.
H_G1 = 0x396C8C005555E1568C00AAAB0000AAAB
H_G2 = 0x5D543A95414E7F1091D50792876A202CD91DE4547085ABAA68A205B2E5A7DDFA628F1CB4D9E82EF21537E293A6691AE1616EC6E786F0C70CF1C38E31C7238E5

# "Effective cofactor" for G1 cofactor clearing per RFC 9380 (1 - x); for G2 we
# clear with the full cofactor via scalar multiplication (correct, if slower
# than the Fuentes et al. endomorphism method).
H_EFF_G1 = 1 - X

# Standard generators (ZCash/IETF convention).
G1_GENERATOR_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_GENERATOR_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1

G2_GENERATOR_X = (
    0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,  # c0
    0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,  # c1
)
G2_GENERATOR_Y = (
    0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,  # c0
    0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,  # c1
)

# Domain separation tag used by Ethereum consensus BLS signatures
# (Lighthouse: crypto/bls/src/impls/blst.rs:14).
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# Serialized sizes (Lighthouse: crypto/bls/src/lib.rs:38-48).
PUBLIC_KEY_BYTES_LEN = 48
SIGNATURE_BYTES_LEN = 96
SECRET_KEY_BYTES_LEN = 32

# --- import-time validation -------------------------------------------------


def _validate() -> None:
    x = X
    r_poly = x**4 - x**2 + 1
    assert R == r_poly, "scalar modulus r does not match BLS12 family polynomial"
    num = (x - 1) ** 2 * r_poly
    assert num % 3 == 0, "BLS12 p(x) numerator not divisible by 3"
    assert P == num // 3 + x, "base modulus p does not match BLS12 family polynomial"
    assert P % 4 == 3, "p = 3 mod 4 expected (sqrt via exponentiation)"
    assert (P * P - 1) % 6 == 0
    # Generator sanity: on curve.
    assert (G1_GENERATOR_Y**2 - G1_GENERATOR_X**3 - B_G1) % P == 0, "G1 generator not on curve"
    # G2 on-curve check in Fp2 = Fp[u]/(u^2+1).
    xc0, xc1 = G2_GENERATOR_X
    yc0, yc1 = G2_GENERATOR_Y
    # x^2 = (c0^2 - c1^2, 2 c0 c1); x^3 = x^2 * x
    s0, s1 = (xc0 * xc0 - xc1 * xc1) % P, (2 * xc0 * xc1) % P
    c0, c1 = (s0 * xc0 - s1 * xc1) % P, (s0 * xc1 + s1 * xc0) % P
    y0, y1 = (yc0 * yc0 - yc1 * yc1) % P, (2 * yc0 * yc1) % P
    assert (y0 - c0 - B_G2[0]) % P == 0 and (y1 - c1 - B_G2[1]) % P == 0, "G2 generator not on curve"
    # Cofactor sanity: h * r == curve order (Hasse bound window).
    n1 = H_G1 * R
    t1 = P + 1 - n1
    assert t1 * t1 <= 4 * P, "G1 cofactor/order violates Hasse bound"
    n2 = H_G2 * R
    t2 = (P * P) + 1 - n2
    assert t2 * t2 <= 4 * P * P, "G2 cofactor/order violates Hasse bound"


_validate()


# Compressed serialization of the G2 point at infinity: the valid signature
# of an empty sync aggregate (spec G2_POINT_AT_INFINITY; sync_aggregate.rs
# SyncAggregate::new).
G2_POINT_AT_INFINITY = b"\xc0" + b"\x00" * 95
