"""BLS12-381 curve constants for the benchmark's own arithmetic.

A copy of the program's constants, kept with the benchmark so that no
change to the program can move the yardstick: traffic generation and the
reference verdicts use these and nothing of `lighthouse_tpu`.
"""

# --- Base field / scalar field -------------------------------------------------

# Field modulus p (381 bits)
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB

# Subgroup order r (255 bits) — order of G1, G2, and GT
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

# BLS curve parameter x (negative). p = (x-1)^2/3 * r + x, r = x^4 - x^2 + 1.
BLS_X = -0xD201000000010000
BLS_X_ABS = 0xD201000000010000

# Curve equations: E/Fp: y^2 = x^3 + 4;  E'/Fp2: y^2 = x^3 + 4(1+u)
B_G1 = 4
B_G2 = (4, 4)  # 4 + 4u in Fp2, represented as (c0, c1)

# Quadratic non-residue used to build Fp2 = Fp[u]/(u^2 + 1): -1.
# Sextic twist / tower constant: xi = 1 + u (Fp6 = Fp2[v]/(v^3 - xi)).
XI = (1, 1)

# --- Generators -----------------------------------------------------------------

G1_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1

G2_X = (
    0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
    0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
)
G2_Y = (
    0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
    0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
)

# --- Cofactors ------------------------------------------------------------------

# G1 cofactor h1 = (x-1)^2 / 3
H1 = (BLS_X - 1) ** 2 // 3
assert (P + 1 - (BLS_X + 1)) == H1 * R, "G1 order sanity: #E(Fp) = h1 * r"

# G2 cofactor (standard constant; sanity-checked in tests by [r]([h2]Q) = inf)
H2 = 0x5D543A95414E7F1091D50792876A202CD91DE4547085ABAA68A205B2E5A7DDFA628F1CB4D9E82EF21537E293A6691AE1616EC6E786F0C70CF1C38E31C7238E5

# --- Ethereum BLS signature ciphersuite ----------------------------------------

# Domain separation tag used by Ethereum consensus (hash-to-G2, SSWU, XMD:SHA-256)
# Matches the DST in the reference client's blst backend.
DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"
