// secp256k1 ECDSA: sign / verify / recover — native backend.
//
// The role of Secp256k1.Native in the reference (Lachain.Crypto,
// DefaultCrypto.cs). The pure-Python implementation in
// lachain_tpu/crypto/ecdsa.py is the semantic oracle: this file reproduces
// its exact wire behavior (RFC 6979 nonce chain incl. the retry tweak,
// low-s normalization with parity-bit flip, the v|=2 flag for r >= n,
// recovery semantics), byte for byte on valid and invalid input alike;
// tests/test_ecdsa.py holds every entry to the oracle.
//
// Arithmetic. The field of p = 2^256 - 2^32 - 977 has a type of its own:
// five 52-bit limbs reduced lazily, a product's high half folded by
// 2^260 mod p = 0x1000003D10, a dedicated squaring, and fixed addition
// chains (255 squarings, 15 multiplications) for the inverse and the square
// root. Scalars mod n stay in generic Montgomery form (a handful of
// operations a call); a public scalar is inverted by binary extended Euclid,
// the nonce by a fixed sliding-window chain over n - 2.
//
// Which algorithm each entry uses:
//  - lt_ec_recover, lt_ec_recover_batch, lt_ec_recover_address_batch and
//    lt_ec_verify, lt_ec_verify_batch: one Strauss-Shamir multiplication
//    u1*P + u2*G. Both scalars split through the endomorphism
//    lambda*(x, y) = (beta*x, y) into two ~128-bit halves, each in wNAF
//    (width 5 for P over an affine table of its odd multiples, width 8 for
//    G over a static affine table), so the four streams share ~129
//    doublings and every addition is mixed Jacobian-affine. Variable time:
//    every input is public. Verification compares x without an inversion.
//  - lt_ec_sign and lt_ec_pubkey: a fixed-base comb over 64 windows of 4
//    bits, the scalar recoded into odd signed digits (an even scalar k
//    becomes n - k and the point is negated), one mixed addition a window,
//    every table entry of a window read under a mask. Constant time in the
//    nonce and the private key: no branch and no memory index depends on
//    either, and the field and scalar arithmetic beneath has no
//    data-dependent branch.
//
// Compiled into libbls381.so alongside the BLS backend (one shared object,
// one ctypes load path).

#include <cstdint>
#include <cstring>
#include <functional>
#include <mutex>
#include <thread>
#include <vector>

namespace secp {

typedef unsigned __int128 u128;
typedef uint64_t u64;
typedef uint32_t u32;
typedef uint8_t u8;

// ---------------------------------------------------------------------------
// 256-bit helpers (little-endian limbs)
// ---------------------------------------------------------------------------

// variable time: public values only
static inline int cmp4(const u64 *a, const u64 *b) {
  for (int i = 3; i >= 0; i--) {
    if (a[i] < b[i]) return -1;
    if (a[i] > b[i]) return 1;
  }
  return 0;
}

static inline bool is_zero4(const u64 *a) {
  return (a[0] | a[1] | a[2] | a[3]) == 0;
}

static inline u64 sub4(u64 *z, const u64 *a, const u64 *b) {
  u64 borrow = 0;
  for (int i = 0; i < 4; i++) {
    u128 cur = (u128)a[i] - b[i] - borrow;
    z[i] = (u64)cur;
    borrow = (u64)(cur >> 127);
  }
  return borrow;
}

static inline u64 add4(u64 *z, const u64 *a, const u64 *b) {
  u128 carry = 0;
  for (int i = 0; i < 4; i++) {
    u128 cur = (u128)a[i] + b[i] + (u64)carry;
    z[i] = (u64)cur;
    carry = cur >> 64;
  }
  return (u64)carry;
}

// all ones if v == 0, else 0; no branch
static inline u64 zero_mask(u64 v) { return ((v | (0 - v)) >> 63) - 1; }

// z = m ? a : b, m all ones or all zeros
static inline void select4(u64 *z, u64 m, const u64 *a, const u64 *b) {
  for (int i = 0; i < 4; i++) z[i] = (a[i] & m) | (b[i] & ~m);
}

// t = a * b, 512 bits
static inline void mul_wide(u64 t[8], const u64 a[4], const u64 b[4]) {
  for (int i = 0; i < 8; i++) t[i] = 0;
  for (int i = 0; i < 4; i++) {
    u64 carry = 0;
    for (int j = 0; j < 4; j++) {
      u128 cur = (u128)a[i] * b[j] + t[i + j] + carry;
      t[i + j] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    t[i + 4] = carry;
  }
}

// `len` < 64 bits of k from bit `pos` (pos public)
static inline u64 bits_at(const u64 k[4], int pos, int len) {
  int l = pos >> 6, s = pos & 63;
  u64 v = k[l] >> s;
  if (s + len > 64 && l < 3) v |= k[l + 1] << (64 - s);
  return v & ((1ull << len) - 1);
}

static void load_be(u64 *z, const u8 *in) {
  for (int i = 0; i < 4; i++) {
    u64 v = 0;
    for (int j = 0; j < 8; j++) v = (v << 8) | in[(3 - i) * 8 + j];
    z[i] = v;
  }
}

static void store_be(u8 *out, const u64 *a) {
  for (int i = 0; i < 4; i++) {
    u64 v = a[3 - i];
    for (int j = 0; j < 8; j++) out[i * 8 + j] = (u8)(v >> (56 - 8 * j));
  }
}

// ---------------------------------------------------------------------------
// the field of p: five 52-bit limbs, value sum n[i] 2^(52i), reduced lazily.
// No function here branches on its inputs.
//
// Bounds, which every caller keeps:
//  - weak: n[0..3] < 2^53, n[4] < 2^48 (what fe_mul, fe_sqr, fe_neg and
//    fe_normalize_weak return; every stored coordinate is weak);
//  - a sum of up to 8 weak elements: fe_sub's second operand;
//  - n[0..3] < 2^58, n[4] < 2^54: fe_mul's and fe_sqr's inputs, which
//    fe_sub's result (first operand a sum of up to 8 weak) meets.
// ---------------------------------------------------------------------------

struct Fe {
  u64 n[5];
};

static const u64 M52 = 0xFFFFFFFFFFFFFull, M48 = 0xFFFFFFFFFFFFull;
static const u64 PC = 0x1000003D1ull;    // 2^256 mod p
static const u64 R52 = 0x1000003D10ull;  // 2^260 mod p
static const u64 FP_M[4] = {0xFFFFFFFEFFFFFC2Full, 0xFFFFFFFFFFFFFFFFull,
                            0xFFFFFFFFFFFFFFFFull, 0xFFFFFFFFFFFFFFFFull};
static const Fe SEVEN = {{7, 0, 0, 0, 0}};
// a cube root of unity mod p, the x-factor of the endomorphism
static const u64 BETA[4] = {0xC1396C28719501EEull, 0x9CF0497512F58995ull,
                            0x6E64479EAC3434E9ull, 0x7AE96A2B657C0710ull};

// v < 2^256 -> a weak element
static inline void fe_from4(Fe &r, const u64 v[4]) {
  r.n[0] = v[0] & M52;
  r.n[1] = (v[0] >> 52 | v[1] << 12) & M52;
  r.n[2] = (v[1] >> 40 | v[2] << 24) & M52;
  r.n[3] = (v[2] >> 28 | v[3] << 36) & M52;
  r.n[4] = v[3] >> 16;
}

// limbs below 2^63 -> weak, the same value mod p
static inline void fe_normalize_weak(Fe &r) {
  u64 t0 = r.n[0], t1 = r.n[1], t2 = r.n[2], t3 = r.n[3], t4 = r.n[4];
  t1 += t0 >> 52;
  t0 &= M52;
  t2 += t1 >> 52;
  t1 &= M52;
  t3 += t2 >> 52;
  t2 &= M52;
  t4 += t3 >> 52;
  t3 &= M52;
  t0 += (t4 >> 48) * PC;
  t4 &= M48;
  t1 += t0 >> 52;
  t0 &= M52;
  r.n[0] = t0;
  r.n[1] = t1;
  r.n[2] = t2;
  r.n[3] = t3;
  r.n[4] = t4;
}

// limbs below 2^63 -> the value mod p in [0, p), 52-bit limbs
static inline void fe_normalize(Fe &r) {
  fe_normalize_weak(r);
  u64 t0 = r.n[0], t1 = r.n[1], t2 = r.n[2], t3 = r.n[3], t4 = r.n[4];
  t2 += t1 >> 52;
  t1 &= M52;
  t3 += t2 >> 52;
  t2 &= M52;
  t4 += t3 >> 52;
  t3 &= M52;
  t0 += (t4 >> 48) * PC;  // below 2^256 + 2^54 before: at most once
  t4 &= M48;
  t1 += t0 >> 52;
  t0 &= M52;
  t2 += t1 >> 52;
  t1 &= M52;
  t3 += t2 >> 52;
  t2 &= M52;
  t4 += t3 >> 52;
  t3 &= M52;
  // below 2^256 now; minus p where v + (2^256 - p) reaches 2^256
  u64 u0 = t0 + PC, u1 = t1 + (u0 >> 52), u2, u3, u4;
  u0 &= M52;
  u2 = t2 + (u1 >> 52);
  u1 &= M52;
  u3 = t3 + (u2 >> 52);
  u2 &= M52;
  u4 = t4 + (u3 >> 52);
  u3 &= M52;
  u64 m = 0 - (u4 >> 48);
  u4 &= M48;
  r.n[0] = (u0 & m) | (t0 & ~m);
  r.n[1] = (u1 & m) | (t1 & ~m);
  r.n[2] = (u2 & m) | (t2 & ~m);
  r.n[3] = (u3 & m) | (t3 & ~m);
  r.n[4] = (u4 & m) | (t4 & ~m);
}

// the value mod p as four 64-bit limbs
static inline void fe_get4(u64 v[4], const Fe &a) {
  Fe t = a;
  fe_normalize(t);
  v[0] = t.n[0] | t.n[1] << 52;
  v[1] = t.n[1] >> 12 | t.n[2] << 40;
  v[2] = t.n[2] >> 24 | t.n[3] << 28;
  v[3] = t.n[3] >> 36 | t.n[4] << 16;
}

// all ones if a = 0 mod p, else 0
static inline u64 fe_zero_mask(const Fe &a) {
  Fe t = a;
  fe_normalize(t);
  return zero_mask(t.n[0] | t.n[1] | t.n[2] | t.n[3] | t.n[4]);
}

static inline void fe_add(Fe &r, const Fe &a, const Fe &b) {
  for (int i = 0; i < 5; i++) r.n[i] = a.n[i] + b.n[i];
}

static inline void fe_mul_int(Fe &r, const Fe &a, u64 k) {
  for (int i = 0; i < 5; i++) r.n[i] = a.n[i] * k;
}

// r = a - b as a + 32p - b
static inline void fe_sub(Fe &r, const Fe &a, const Fe &b) {
  r.n[0] = a.n[0] + 0x1FFFFDFFFFF85E0ull - b.n[0];
  r.n[1] = a.n[1] + 0x1FFFFFFFFFFFFE0ull - b.n[1];
  r.n[2] = a.n[2] + 0x1FFFFFFFFFFFFE0ull - b.n[2];
  r.n[3] = a.n[3] + 0x1FFFFFFFFFFFFE0ull - b.n[3];
  r.n[4] = a.n[4] + 0x1FFFFFFFFFFFE0ull - b.n[4];
}

// r = -a, weak
static inline void fe_neg(Fe &r, const Fe &a) {
  static const Fe zero = {{0, 0, 0, 0, 0}};
  fe_sub(r, zero, a);
  fe_normalize_weak(r);
}

// r = c mod p for the nine columns c of a product (each below 2^118).
// Columns 5..8 weigh 2^260 = R52 and more: c_k = lo + hi 2^64 folds as
// lo * R52 (< 2^101) into column k-5 and hi * R52 (< 2^91) 12 bits up into
// column k-4. The five columns left carry into 52-bit limbs, and what lies
// above 2^256 folds by PC into the lowest.
static inline void fe_reduce(Fe &r, u128 c[9]) {
  for (int k = 5; k < 9; k++) {
    c[k - 5] += (u128)(u64)c[k] * R52;
    c[k - 4] += (u128)(u64)(c[k] >> 64) * R52 << 12;
  }
  u128 acc = c[0];
  u64 t0 = (u64)acc & M52;
  acc = (acc >> 52) + c[1];
  u64 t1 = (u64)acc & M52;
  acc = (acc >> 52) + c[2];
  u64 t2 = (u64)acc & M52;
  acc = (acc >> 52) + c[3];
  u64 t3 = (u64)acc & M52;
  acc = (acc >> 52) + c[4];
  r.n[4] = (u64)acc & M48;
  acc >>= 48;  // below 2^72
  acc = (u128)(u64)acc * PC + ((u128)((u64)(acc >> 64) * PC) << 64) + t0;
  r.n[0] = (u64)acc & M52;
  t1 += (u64)(acc >> 52);
  r.n[1] = t1 & M52;
  r.n[2] = t2 + (t1 >> 52);
  r.n[3] = t3;
}

static inline void fe_mul(Fe &r, const Fe &a, const Fe &b) {
  const u64 *x = a.n, *y = b.n;
  u128 c[9];
  c[0] = (u128)x[0] * y[0];
  c[1] = (u128)x[0] * y[1] + (u128)x[1] * y[0];
  c[2] = (u128)x[0] * y[2] + (u128)x[1] * y[1] + (u128)x[2] * y[0];
  c[3] = (u128)x[0] * y[3] + (u128)x[1] * y[2] + (u128)x[2] * y[1] +
         (u128)x[3] * y[0];
  c[4] = (u128)x[0] * y[4] + (u128)x[1] * y[3] + (u128)x[2] * y[2] +
         (u128)x[3] * y[1] + (u128)x[4] * y[0];
  c[5] = (u128)x[1] * y[4] + (u128)x[2] * y[3] + (u128)x[3] * y[2] +
         (u128)x[4] * y[1];
  c[6] = (u128)x[2] * y[4] + (u128)x[3] * y[3] + (u128)x[4] * y[2];
  c[7] = (u128)x[3] * y[4] + (u128)x[4] * y[3];
  c[8] = (u128)x[4] * y[4];
  fe_reduce(r, c);
}

static inline void fe_sqr(Fe &r, const Fe &a) {
  const u64 *x = a.n;
  u64 d0 = 2 * x[0], d1 = 2 * x[1], d2 = 2 * x[2], d3 = 2 * x[3];
  u128 c[9];
  c[0] = (u128)x[0] * x[0];
  c[1] = (u128)d0 * x[1];
  c[2] = (u128)d0 * x[2] + (u128)x[1] * x[1];
  c[3] = (u128)d0 * x[3] + (u128)d1 * x[2];
  c[4] = (u128)d0 * x[4] + (u128)d1 * x[3] + (u128)x[2] * x[2];
  c[5] = (u128)d1 * x[4] + (u128)d2 * x[3];
  c[6] = (u128)d2 * x[4] + (u128)x[3] * x[3];
  c[7] = (u128)d3 * x[4];
  c[8] = (u128)x[4] * x[4];
  fe_reduce(r, c);
}

static void fe_sqrn(Fe &r, const Fe &a, int n) {
  fe_sqr(r, a);
  for (int i = 1; i < n; i++) fe_sqr(r, r);
}

// the shared head of both chains: t = a^(2^246 - 2^23 + 2^22 - 1), i.e.
// 223 ones, a zero, 22 ones; x2 = a^3
static void fe_chain_head(Fe &t, Fe &x2, const Fe &a) {
  Fe x3, x6, x9, x11, x22, x44, x88, x176;
  fe_sqr(x2, a);
  fe_mul(x2, x2, a);
  fe_sqr(x3, x2);
  fe_mul(x3, x3, a);
  fe_sqrn(x6, x3, 3);
  fe_mul(x6, x6, x3);
  fe_sqrn(x9, x6, 3);
  fe_mul(x9, x9, x3);
  fe_sqrn(x11, x9, 2);
  fe_mul(x11, x11, x2);
  fe_sqrn(x22, x11, 11);
  fe_mul(x22, x22, x11);
  fe_sqrn(x44, x22, 22);
  fe_mul(x44, x44, x22);
  fe_sqrn(x88, x44, 44);
  fe_mul(x88, x88, x44);
  fe_sqrn(x176, x88, 88);
  fe_mul(x176, x176, x88);
  fe_sqrn(t, x176, 44);  // x220
  fe_mul(t, t, x44);
  fe_sqrn(t, t, 3);  // x223
  fe_mul(t, t, x3);
  fe_sqrn(t, t, 23);
  fe_mul(t, t, x22);
}

// r = a^(p-2) = a^-1 (0 for 0)
static void fe_inv(Fe &r, const Fe &a) {
  Fe t, x2;
  fe_chain_head(t, x2, a);
  fe_sqrn(t, t, 5);
  fe_mul(t, t, a);
  fe_sqrn(t, t, 3);
  fe_mul(t, t, x2);
  fe_sqrn(t, t, 2);
  fe_mul(r, t, a);
}

// r = a^((p+1)/4): the square root of a where one exists
static void fe_sqrt(Fe &r, const Fe &a) {
  Fe t, x2;
  fe_chain_head(t, x2, a);
  fe_sqrn(t, t, 6);
  fe_mul(t, t, x2);
  fe_sqrn(r, t, 2);
}

// ---------------------------------------------------------------------------
// scalars mod n: generic 4x64 Montgomery, no data-dependent branch
// ---------------------------------------------------------------------------

struct Mod {
  u64 m[4];    // modulus, little-endian limbs
  u64 inv;     // -m^-1 mod 2^64
  u64 r2[4];   // (2^256)^2 mod m
};

static const Mod FN = {
    {0xBFD25E8CD0364141ull, 0xBAAEDCE6AF48A03Bull, 0xFFFFFFFFFFFFFFFEull,
     0xFFFFFFFFFFFFFFFFull},
    0x4B0DFF665588B13Full,
    // 2^512 mod n
    {0x896CF21467D7D140ull, 0x741496C20E7CF878ull, 0xE697F5E45BCD07C6ull,
     0x9D671CD581C69BC5ull},
};

// floor(n / 2)
static const u64 HALF_N[4] = {0xDFE92F46681B20A0ull, 0x5D576E7357A4501Dull,
                              0xFFFFFFFFFFFFFFFFull, 0x7FFFFFFFFFFFFFFFull};

// z = a + b mod m, for a, b < m
static void mod_add(const Mod &M, u64 *z, const u64 *a, const u64 *b) {
  u64 t[4], s[4];
  u64 carry = add4(t, a, b);
  u64 borrow = sub4(s, t, M.m);
  select4(z, 0 - (carry | (borrow ^ 1)), s, t);
}

// Montgomery product: z = a * b * 2^-256 mod m (CIOS)
static void mont_mul(const Mod &M, u64 *z, const u64 *a, const u64 *b) {
  u64 t[6];
  memset(t, 0, sizeof(t));
  for (int i = 0; i < 4; i++) {
    u64 carry = 0;
    for (int j = 0; j < 4; j++) {
      u128 cur = (u128)a[i] * b[j] + t[j] + carry;
      t[j] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    u128 cur = (u128)t[4] + carry;
    t[4] = (u64)cur;
    t[5] = (u64)(cur >> 64);

    u64 mfac = t[0] * M.inv;
    u128 c2 = (u128)mfac * M.m[0] + t[0];
    carry = (u64)(c2 >> 64);
    for (int j = 1; j < 4; j++) {
      u128 c3 = (u128)mfac * M.m[j] + t[j] + carry;
      t[j - 1] = (u64)c3;
      carry = (u64)(c3 >> 64);
    }
    u128 c4 = (u128)t[4] + carry;
    t[3] = (u64)c4;
    t[4] = t[5] + (u64)(c4 >> 64);
    t[5] = 0;
  }
  u64 s[4];
  u64 borrow = sub4(s, t, M.m);
  select4(z, 0 - (t[4] | (borrow ^ 1)), s, t);
}

static void to_mont(const Mod &M, u64 *z, const u64 *a) {
  mont_mul(M, z, a, M.r2);
}

static void from_mont(const Mod &M, u64 *z, const u64 *a) {
  u64 one[4] = {1, 0, 0, 0};
  mont_mul(M, z, a, one);
}

// z = a * b mod n, plain in, plain out (both below n)
static void fn_mul(u64 *z, const u64 *a, const u64 *b) {
  u64 t[4];
  mont_mul(FN, t, a, b);
  mont_mul(FN, z, t, FN.r2);
}

// z = a^(n-2) = a^-1 mod n, Montgomery in and out: a sliding window of 5
// bits over the fixed exponent, so the sequence of operations is the same
// for every a
static void fn_inv(u64 *z, const u64 *a) {
  static const u64 E[4] = {0xBFD25E8CD036413Full, 0xBAAEDCE6AF48A03Bull,
                           0xFFFFFFFFFFFFFFFEull, 0xFFFFFFFFFFFFFFFFull};
  u64 pw[16][4], a2[4], acc[4];  // pw[i] = a^(2i+1)
  mont_mul(FN, a2, a, a);
  memcpy(pw[0], a, 32);
  for (int i = 1; i < 16; i++) mont_mul(FN, pw[i], pw[i - 1], a2);
  bool started = false;
  int i = 255;
  while (i >= 0) {
    if (!bits_at(E, i, 1)) {
      mont_mul(FN, acc, acc, acc);
      i--;
      continue;
    }
    int lo = i >= 4 ? i - 4 : 0;
    while (!bits_at(E, lo, 1)) lo++;
    unsigned w = (unsigned)bits_at(E, lo, i - lo + 1);
    if (started) {
      for (int k = lo; k <= i; k++) mont_mul(FN, acc, acc, acc);
      mont_mul(FN, acc, acc, pw[w >> 1]);
    } else {
      memcpy(acc, pw[w >> 1], 32);
      started = true;
    }
    i = lo - 1;
  }
  memcpy(z, acc, 32);
}

// x / 2 mod n
static inline void fn_halve(u64 x[4]) {
  u64 carry = (x[0] & 1) ? add4(x, x, FN.m) : 0;
  x[0] = x[0] >> 1 | x[1] << 63;
  x[1] = x[1] >> 1 | x[2] << 63;
  x[2] = x[2] >> 1 | x[3] << 63;
  x[3] = x[3] >> 1 | carry << 63;
}

// z = a - b mod n, for a, b < n
static inline void fn_sub(u64 z[4], const u64 a[4], const u64 b[4]) {
  if (sub4(z, a, b)) add4(z, z, FN.m);
}

// v >>= s, 0 < s < 64
static inline void shr4(u64 v[4], int s) {
  v[0] = v[0] >> s | v[1] << (64 - s);
  v[1] = v[1] >> s | v[2] << (64 - s);
  v[2] = v[2] >> s | v[3] << (64 - s);
  v[3] >>= s;
}

// a^-1 mod n for a public 0 < a < n, plain in and out: binary extended
// Euclid, variable time. x1 a = u and x2 a = v (mod n) throughout
static void fn_inv_var(u64 z[4], const u64 a[4]) {
  static const u64 one[4] = {1, 0, 0, 0};
  u64 u[4], v[4], x1[4] = {1, 0, 0, 0}, x2[4] = {0, 0, 0, 0};
  memcpy(u, a, 32);
  memcpy(v, FN.m, 32);
  while (cmp4(u, one) != 0 && cmp4(v, one) != 0) {
    if (!(u[0] & 1)) {
      int s = u[0] ? __builtin_ctzll(u[0]) : 63;
      shr4(u, s);
      for (int i = 0; i < s; i++) fn_halve(x1);
      continue;
    }
    if (!(v[0] & 1)) {
      int s = v[0] ? __builtin_ctzll(v[0]) : 63;
      shr4(v, s);
      for (int i = 0; i < s; i++) fn_halve(x2);
      continue;
    }
    if (cmp4(u, v) >= 0) {
      sub4(u, u, v);
      fn_sub(x1, x1, x2);
    } else {
      sub4(v, v, u);
      fn_sub(x2, x2, x1);
    }
  }
  memcpy(z, cmp4(u, one) == 0 ? x1 : x2, 32);
}

// 1 if 0 < k < n, else 0; no branch
static inline u64 scalar_valid(const u64 k[4]) {
  u64 t[4];
  u64 below_n = sub4(t, k, FN.m);
  return below_n & ~zero_mask(k[0] | k[1] | k[2] | k[3]) & 1;
}

// ---------------------------------------------------------------------------
// GLV: k = k1 + k2 * lambda (mod n) with |k1|, |k2| < 2^128
// ---------------------------------------------------------------------------

static const u64 MINUS_LAMBDA[4] = {0xE0CFC810B51283CFull, 0xA880B9FC8EC739C2ull,
                                    0x5AD9E3FD77ED9BA4ull, 0xAC9C52B33FA3CF1Full};
static const u64 MINUS_B1[4] = {0x6F547FA90ABFE4C3ull, 0xE4437ED6010E8828ull, 0,
                                0};
static const u64 MINUS_B2[4] = {0xD765CDA83DB1562Cull, 0x8A280AC50774346Dull,
                                0xFFFFFFFFFFFFFFFEull, 0xFFFFFFFFFFFFFFFFull};
static const u64 GLV_G1[4] = {0xE893209A45DBB031ull, 0x3DAA8A1471E8CA7Full,
                              0xE86C90E49284EB15ull, 0x3086D221A7D46BCDull};
static const u64 GLV_G2[4] = {0x1571B4AE8AC47F71ull, 0x221208AC9DF506C6ull,
                              0x6F547FA90ABFE4C4ull, 0xE4437ED6010E8828ull};

// r = round(a * b / 2^384)
static void mul_shift_384(u64 r[4], const u64 a[4], const u64 b[4]) {
  u64 t[8];
  mul_wide(t, a, b);
  u128 c = (u128)t[6] + (t[5] >> 63);
  r[0] = (u64)c;
  r[1] = t[7] + (u64)(c >> 64);
  r[2] = r[3] = 0;
}

static void split_lambda(u64 k1[4], u64 k2[4], const u64 k[4]) {
  u64 c1[4], c2[4];
  mul_shift_384(c1, k, GLV_G1);
  mul_shift_384(c2, k, GLV_G2);
  fn_mul(c1, c1, MINUS_B1);
  fn_mul(c2, c2, MINUS_B2);
  mod_add(FN, k2, c1, c2);
  fn_mul(k1, k2, MINUS_LAMBDA);
  mod_add(FN, k1, k1, k);
}

// k (a half of a split) as a magnitude below 2^128 and a sign
static bool scalar_abs(u64 k[4]) {
  if (cmp4(k, HALF_N) <= 0) return false;
  sub4(k, FN.m, k);
  return true;
}

// width-w NAF of k < 2^129: digits odd in (-2^(w-1), 2^(w-1)) or 0, at most
// one nonzero in any w consecutive; returns the number of digits
static const int WNAF_BITS = 130;

static int wnaf(int naf[WNAF_BITS], const u64 k[4], int w) {
  memset(naf, 0, sizeof(int) * WNAF_BITS);
  int carry = 0, bit = 0, len = 0;
  while (bit < WNAF_BITS) {
    if ((int)bits_at(k, bit, 1) == carry) {
      bit++;
      continue;
    }
    int now = w < WNAF_BITS - bit ? w : WNAF_BITS - bit;
    int word = (int)bits_at(k, bit, now) + carry;
    carry = (word >> (w - 1)) & 1;
    word -= carry << w;
    naf[bit] = word;
    len = bit + 1;
    bit += now;
  }
  return len;
}

// ---------------------------------------------------------------------------
// the group, y^2 = x^3 + 7: affine and Jacobian points, weak coordinates
// ---------------------------------------------------------------------------

struct Ge {
  Fe x, y;
};

struct Gej {
  Fe x, y, z;
  bool inf;
};

static const u64 GX[4] = {0x59F2815B16F81798ull, 0x029BFCDB2DCE28D9ull,
                          0x55A06295CE870B07ull, 0x79BE667EF9DCBBACull};
static const u64 GY[4] = {0x9C47D08FFB10D4B8ull, 0xFD17B448A6855419ull,
                          0x5DA4FBFC0E1108A8ull, 0x483ADA7726A3C465ull};

static void gej_set_ge(Gej &r, const Ge &a) {
  r.x = a.x;
  r.y = a.y;
  r.z = Fe{{1, 0, 0, 0, 0}};
  r.inf = false;
}

// r = 2a (dbl-2009-l); no branch, infinity stays infinity. y is never 0:
// the group has odd order
static void gej_dbl(Gej &r, const Gej &a) {
  Fe A, B, C, D, E, F, t;
  Fe x3, y3, z3;
  fe_sqr(A, a.x);
  fe_sqr(B, a.y);
  fe_sqr(C, B);
  fe_add(t, a.x, B);
  fe_sqr(D, t);
  fe_add(t, A, C);
  fe_sub(D, D, t);
  fe_normalize_weak(D);
  fe_add(D, D, D);  // 2((X+B)^2 - A - C)
  fe_mul_int(E, A, 3);
  fe_sqr(F, E);
  fe_add(t, D, D);
  fe_sub(x3, F, t);  // F - 2D
  fe_normalize_weak(x3);
  fe_sub(t, D, x3);
  fe_mul(y3, E, t);
  fe_mul_int(t, C, 8);
  fe_sub(y3, y3, t);
  fe_normalize_weak(y3);
  fe_mul(z3, a.y, a.z);
  fe_add(z3, z3, z3);
  fe_normalize_weak(z3);
  r.x = x3;
  r.y = y3;
  r.z = z3;
  r.inf = a.inf;
}

// the two differences of a mixed addition a + b: h = U2 - X1, rr = S2 - Y1,
// with z for a's z in U2 = b.x z^2 and S2 = b.y z^3
static inline void madd_h_r(Fe &h, Fe &rr, const Gej &a, const Ge &b,
                            const Fe &z) {
  Fe zz, u2, s2;
  fe_sqr(zz, z);
  fe_mul(u2, b.x, zz);
  fe_mul(s2, b.y, z);
  fe_mul(s2, s2, zz);
  fe_sub(h, u2, a.x);
  fe_sub(rr, s2, a.y);
}

// the rest of the mixed addition (madd-2004-hmv, 8M + 3S in all), valid for
// a != +-b and a finite; no branch
static inline void madd_tail(Gej &r, const Gej &a, const Fe &h,
                             const Fe &rr) {
  Fe hh, hhh, v, x3, y3, z3, t;
  fe_sqr(hh, h);
  fe_mul(hhh, h, hh);
  fe_mul(v, a.x, hh);
  fe_sqr(x3, rr);
  fe_add(t, v, v);
  fe_add(t, t, hhh);
  fe_sub(x3, x3, t);
  fe_normalize_weak(x3);
  fe_sub(t, v, x3);
  fe_mul(y3, t, rr);
  fe_mul(t, a.y, hhh);
  fe_sub(y3, y3, t);
  fe_normalize_weak(y3);
  fe_mul(z3, a.z, h);
  r.x = x3;
  r.y = y3;
  r.z = z3;
  r.inf = false;
}

// r = a + b for any a (variable time: public points). With zt, a lies on
// the curve of scale zt that ecmult's accumulator runs on and b on the
// curve itself, so b enters as (x zt^2, y zt^3)
static void gej_add_ge_var(Gej &r, const Gej &a, const Ge &b,
                           const Fe *zt = nullptr) {
  if (a.inf) {
    gej_set_ge(r, b);
    if (zt) {
      Fe z2, z3;
      fe_sqr(z2, *zt);
      fe_mul(z3, z2, *zt);
      fe_mul(r.x, b.x, z2);
      fe_mul(r.y, b.y, z3);
    }
    return;
  }
  Fe h, rr, az;
  if (zt) {
    fe_mul(az, a.z, *zt);
  } else {
    az = a.z;
  }
  madd_h_r(h, rr, a, b, az);
  if (fe_zero_mask(h)) {
    if (fe_zero_mask(rr)) {
      gej_dbl(r, a);
    } else {
      r.inf = true;
    }
    return;
  }
  madd_tail(r, a, h, rr);
}

// r = a + b, both Jacobian (table building only)
static void gej_add_var(Gej &r, const Gej &a, const Gej &b) {
  if (a.inf) {
    r = b;
    return;
  }
  if (b.inf) {
    r = a;
    return;
  }
  Fe z1z1, z2z2, u1, u2, s1, s2, h, rr, t;
  fe_sqr(z1z1, a.z);
  fe_sqr(z2z2, b.z);
  fe_mul(u1, a.x, z2z2);
  fe_mul(u2, b.x, z1z1);
  fe_mul(t, a.y, b.z);
  fe_mul(s1, t, z2z2);
  fe_mul(t, b.y, a.z);
  fe_mul(s2, t, z1z1);
  fe_sub(h, u2, u1);
  fe_sub(rr, s2, s1);
  if (fe_zero_mask(h)) {
    if (fe_zero_mask(rr)) {
      gej_dbl(r, a);
    } else {
      r.inf = true;
    }
    return;
  }
  Fe hh, hhh, v, x3, y3, z3;
  fe_sqr(hh, h);
  fe_mul(hhh, h, hh);
  fe_mul(v, u1, hh);
  fe_sqr(x3, rr);
  fe_add(t, v, v);
  fe_add(t, t, hhh);
  fe_sub(x3, x3, t);
  fe_normalize_weak(x3);
  fe_sub(t, v, x3);
  fe_mul(y3, t, rr);
  fe_mul(t, s1, hhh);
  fe_sub(y3, y3, t);
  fe_normalize_weak(y3);
  fe_mul(z3, a.z, b.z);
  fe_mul(z3, z3, h);
  r.x = x3;
  r.y = y3;
  r.z = z3;
  r.inf = false;
}

// out[i] = (2i+1) * p for i < n
static void odd_multiples(Gej *out, const Gej &p, int n) {
  Gej d;
  gej_dbl(d, p);
  out[0] = p;
  for (int i = 1; i < n; i++) gej_add_var(out[i], out[i - 1], d);
}

// affine forms of n finite points, one inversion (Montgomery's trick);
// acc: n elements of scratch
static void batch_to_affine(Ge *out, const Gej *in, int n, Fe *acc) {
  acc[0] = in[0].z;
  for (int i = 1; i < n; i++) fe_mul(acc[i], acc[i - 1], in[i].z);
  Fe inv, zi, zi2;
  fe_inv(inv, acc[n - 1]);
  for (int i = n - 1; i >= 0; i--) {
    if (i > 0) {
      fe_mul(zi, inv, acc[i - 1]);
      fe_mul(inv, inv, in[i].z);
    } else {
      zi = inv;
    }
    fe_sqr(zi2, zi);
    fe_mul(out[i].x, in[i].x, zi2);
    fe_mul(zi2, zi2, zi);
    fe_mul(out[i].y, in[i].y, zi2);
  }
}

// affine x, y of p as plain limbs; false for infinity
static bool gej_affine(u64 ax[4], u64 ay[4], const Gej &p) {
  if (p.inf) return false;
  Fe zi, zi2, t;
  fe_inv(zi, p.z);
  fe_sqr(zi2, zi);
  fe_mul(t, p.x, zi2);
  fe_get4(ax, t);
  fe_mul(zi2, zi2, zi);
  fe_mul(t, p.y, zi2);
  fe_get4(ay, t);
  return true;
}

// the point with x and the given parity of y; false if x >= p or x^3 + 7
// has no square root
static bool ge_decompress(Ge &p, const u64 x[4], unsigned odd) {
  if (cmp4(x, FP_M) >= 0) return false;
  Fe fx, y2, y, chk;
  fe_from4(fx, x);
  fe_sqr(y2, fx);
  fe_mul(y2, y2, fx);
  fe_add(y2, y2, SEVEN);
  fe_sqrt(y, y2);
  fe_sqr(chk, y);
  fe_sub(chk, chk, y2);
  if (!fe_zero_mask(chk)) return false;
  fe_normalize(y);
  if ((unsigned)(y.n[0] & 1) != odd) fe_neg(y, y);
  p.x = fx;
  p.y = y;
  return true;
}

// ---------------------------------------------------------------------------
// tables of G, built once a process (~0.2 ms)
// ---------------------------------------------------------------------------

static const int G_WNAF = 10;                     // wNAF width of G's streams
static const int G_ODD_N = 1 << (G_WNAF - 2);     // 256
static const int P_WNAF = 5;                      // wNAF width of P's streams
static const int P_ODD_N = 1 << (P_WNAF - 2);     // 8

static Fe BETA_FE;
static Ge G_ODD[G_ODD_N];      // (2i+1) * G
static Ge G_ODD_LAM[G_ODD_N];  // (2i+1) * lambda * G = (beta x, y)
static Ge G_COMB[64][8];       // (2j+1) * 16^w * G, the signing comb
static std::once_flag tables_once;

static void build_tables() {
  fe_from4(BETA_FE, BETA);
  std::vector<Gej> jac(64 * 8);
  std::vector<Fe> scratch(64 * 8);
  Ge ga;
  fe_from4(ga.x, GX);
  fe_from4(ga.y, GY);
  Gej g;
  gej_set_ge(g, ga);
  odd_multiples(jac.data(), g, G_ODD_N);
  batch_to_affine(G_ODD, jac.data(), G_ODD_N, scratch.data());
  for (int i = 0; i < G_ODD_N; i++) {
    fe_mul(G_ODD_LAM[i].x, G_ODD[i].x, BETA_FE);
    G_ODD_LAM[i].y = G_ODD[i].y;
  }
  Gej base = g;
  for (int w = 0; w < 64; w++) {
    odd_multiples(&jac[8 * w], base, 8);
    for (int d = 0; d < 4; d++) gej_dbl(base, base);
  }
  batch_to_affine(&G_COMB[0][0], jac.data(), 64 * 8, scratch.data());
}

static void ensure_tables() { std::call_once(tables_once, build_tables); }

// ---------------------------------------------------------------------------
// variable base: r = na * p + ng * G (Strauss-Shamir over GLV halves in
// wNAF); public inputs, variable time
// ---------------------------------------------------------------------------

static inline void add_digit(Gej &r, const int *naf, int len, int i,
                             const Ge *tab, bool neg, const Fe *zt) {
  if (i >= len || !naf[i]) return;
  int d = naf[i];
  Ge t = tab[((d < 0 ? -d : d) - 1) >> 1];
  if ((d < 0) != neg) fe_neg(t.y, t.y);
  gej_add_ge_var(r, r, t, zt);
}

static void ecmult(Gej &r, const Ge &p, const u64 na[4], const u64 ng[4]) {
  ensure_tables();
  u64 a1[4], a2[4], g1[4], g2[4];
  split_lambda(a1, a2, na);
  split_lambda(g1, g2, ng);
  bool na1 = scalar_abs(a1), na2 = scalar_abs(a2);
  bool ng1 = scalar_abs(g1), ng2 = scalar_abs(g2);
  int wa1[WNAF_BITS], wa2[WNAF_BITS], wg1[WNAF_BITS], wg2[WNAF_BITS];
  int la1 = wnaf(wa1, a1, P_WNAF), la2 = wnaf(wa2, a2, P_WNAF);
  int lg1 = wnaf(wg1, g1, G_WNAF), lg2 = wnaf(wg2, g2, G_WNAF);
  // P's odd multiples without an inversion. On the curve isomorphic by the
  // z of d = 2P, d is the affine (X, Y) and P is (x z^2, y z^3), and the
  // addition formulas never read the curve's constant: the multiples are
  // mixed additions of d there. Their z-ratios (each addition's h) then
  // bring every multiple to the last one's z, which makes them all affine
  // on one more curve, the one the accumulator runs on; zt is its scale, a
  // point (X, Y, Z) there being (X / (Z zt)^2, Y / (Z zt)^3). G's points
  // enter it scaled (gej_add_ge_var), and r's z takes zt at the end.
  Ge pa[P_ODD_N], pl[P_ODD_N];
  Fe zt = {{1, 0, 0, 0, 0}};
  if (la1 || la2) {
    Gej pj, d, tj[P_ODD_N];
    Ge da;
    Fe zr[P_ODD_N], z2, z3, s, s2, rr;
    gej_set_ge(pj, p);
    gej_dbl(d, pj);
    da.x = d.x;
    da.y = d.y;
    fe_sqr(z2, d.z);
    fe_mul(z3, z2, d.z);
    gej_set_ge(tj[0], p);
    fe_mul(tj[0].x, p.x, z2);
    fe_mul(tj[0].y, p.y, z3);
    // (2i+1) P - 2P is never +-2P: no exceptional case
    for (int i = 1; i < P_ODD_N; i++) {
      madd_h_r(zr[i], rr, tj[i - 1], da, tj[i - 1].z);
      madd_tail(tj[i], tj[i - 1], zr[i], rr);
    }
    for (int i = P_ODD_N - 1; i >= 0; i--) {
      if (i == P_ODD_N - 1) {
        pa[i].x = tj[i].x;
        pa[i].y = tj[i].y;
        s = zr[i];
      } else {  // s = z_last / z_i
        fe_sqr(s2, s);
        fe_mul(pa[i].x, tj[i].x, s2);
        fe_mul(s2, s2, s);
        fe_mul(pa[i].y, tj[i].y, s2);
        if (i > 0) fe_mul(s, s, zr[i]);
      }
      fe_mul(pl[i].x, pa[i].x, BETA_FE);
      pl[i].y = pa[i].y;
    }
    fe_mul(zt, tj[P_ODD_N - 1].z, d.z);
  }
  int len = la1;
  if (la2 > len) len = la2;
  if (lg1 > len) len = lg1;
  if (lg2 > len) len = lg2;
  r.inf = true;
  for (int i = len - 1; i >= 0; i--) {
    if (!r.inf) gej_dbl(r, r);
    add_digit(r, wa1, la1, i, pa, na1, nullptr);
    add_digit(r, wa2, la2, i, pl, na2, nullptr);
    add_digit(r, wg1, lg1, i, G_ODD, ng1, &zt);
    add_digit(r, wg2, lg2, i, G_ODD_LAM, ng2, &zt);
  }
  if (!r.inf) fe_mul(r.z, r.z, zt);
}

// ---------------------------------------------------------------------------
// fixed base, constant time: affine k * G for a secret 0 < k < n
// ---------------------------------------------------------------------------

// window w's entry for the 4-bit value b, whose signed odd digit is 2b - 15:
// every entry of the window is read, the one wanted kept under a mask, and
// y negated under a mask for a negative digit
static inline void comb_lookup(Ge &out, int w, u64 b) {
  u64 neg = (b >> 3) ^ 1;
  u64 idx = b ^ (7 + (b >> 3));  // (|2b - 15| - 1) / 2
  memset(&out, 0, sizeof(out));
  for (u64 j = 0; j < 8; j++) {
    u64 m = zero_mask(j ^ idx);
    for (int l = 0; l < 5; l++) {
      out.x.n[l] |= G_COMB[w][j].x.n[l] & m;
      out.y.n[l] |= G_COMB[w][j].y.n[l] & m;
    }
  }
  Fe ny;
  fe_neg(ny, out.y);
  u64 m = 0 - neg;
  for (int l = 0; l < 5; l++)
    out.y.n[l] = (ny.n[l] & m) | (out.y.n[l] & ~m);
}

static void ecmult_gen(u64 ax[4], u64 ay[4], const u64 k[4]) {
  ensure_tables();
  // an odd scalar e (k, or n - k with the point negated at the end) is
  // sum_w d_w 16^w with d_w = 2 * bits(e, 4w+1, 4) - 15 for w < 63 and
  // d_63 = 2 * bits(e, 253, 3) + 1: 64 odd digits, none zero
  u64 nk[4], e[4];
  sub4(nk, FN.m, k);
  u64 flip = 0 - ((k[0] & 1) ^ 1);
  select4(e, flip, nk, k);
  Gej acc;
  Ge t;
  Fe h, rr;
  comb_lookup(t, 0, bits_at(e, 1, 4));
  gej_set_ge(acc, t);
  // |partial sum| < 16^w <= |next digit| * 16^w and both below n / 2: no
  // addition before the last meets an equal or opposite point
  for (int w = 1; w < 63; w++) {
    comb_lookup(t, w, bits_at(e, 4 * w + 1, 4));
    madd_h_r(h, rr, acc, t, acc.z);
    madd_tail(acc, acc, h, rr);
  }
  // the last may meet an equal point (never the opposite: e != 0 mod n):
  // the doubling is computed too and kept under a mask where h == 0
  comb_lookup(t, 63, bits_at(e, 253, 3) | 8);
  madd_h_r(h, rr, acc, t, acc.z);
  Gej sum, dbl;
  madd_tail(sum, acc, h, rr);
  gej_dbl(dbl, acc);
  u64 hz = fe_zero_mask(h);
  for (int l = 0; l < 5; l++) {
    sum.x.n[l] = (dbl.x.n[l] & hz) | (sum.x.n[l] & ~hz);
    sum.y.n[l] = (dbl.y.n[l] & hz) | (sum.y.n[l] & ~hz);
    sum.z.n[l] = (dbl.z.n[l] & hz) | (sum.z.n[l] & ~hz);
  }
  Fe zi, zi2, x, y, ny;
  fe_inv(zi, sum.z);
  fe_sqr(zi2, zi);
  fe_mul(x, sum.x, zi2);
  fe_mul(zi2, zi2, zi);
  fe_mul(y, sum.y, zi2);
  fe_neg(ny, y);
  fe_get4(ax, x);
  u64 y4[4], ny4[4];
  fe_get4(y4, y);
  fe_get4(ny4, ny);
  select4(ay, flip, ny4, y4);
}

// ---------------------------------------------------------------------------
// SHA-256 + HMAC (for the RFC 6979 nonce chain)
// ---------------------------------------------------------------------------

static const u32 K256[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

struct Sha256 {
  u32 h[8];
  u8 buf[64];
  u64 total;
  size_t fill;
};

static inline u32 rotr(u32 v, int s) { return (v >> s) | (v << (32 - s)); }

static void sha_init(Sha256 &s) {
  static const u32 H0[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                            0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};
  memcpy(s.h, H0, sizeof(H0));
  s.total = 0;
  s.fill = 0;
}

static void sha_block(Sha256 &s, const u8 *p) {
  u32 w[64];
  for (int i = 0; i < 16; i++)
    w[i] = ((u32)p[4 * i] << 24) | ((u32)p[4 * i + 1] << 16) |
           ((u32)p[4 * i + 2] << 8) | p[4 * i + 3];
  for (int i = 16; i < 64; i++) {
    u32 s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    u32 s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  u32 a = s.h[0], b = s.h[1], c = s.h[2], d = s.h[3], e = s.h[4], f = s.h[5],
      g = s.h[6], hh = s.h[7];
  for (int i = 0; i < 64; i++) {
    u32 S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    u32 ch = (e & f) ^ (~e & g);
    u32 t1 = hh + S1 + ch + K256[i] + w[i];
    u32 S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    u32 maj = (a & b) ^ (a & c) ^ (b & c);
    u32 t2 = S0 + maj;
    hh = g;
    g = f;
    f = e;
    e = d + t1;
    d = c;
    c = b;
    b = a;
    a = t1 + t2;
  }
  s.h[0] += a;
  s.h[1] += b;
  s.h[2] += c;
  s.h[3] += d;
  s.h[4] += e;
  s.h[5] += f;
  s.h[6] += g;
  s.h[7] += hh;
}

static void sha_update(Sha256 &s, const u8 *data, size_t len) {
  s.total += len;
  while (len) {
    size_t take = 64 - s.fill;
    if (take > len) take = len;
    memcpy(s.buf + s.fill, data, take);
    s.fill += take;
    data += take;
    len -= take;
    if (s.fill == 64) {
      sha_block(s, s.buf);
      s.fill = 0;
    }
  }
}

static void sha_final(Sha256 &s, u8 out[32]) {
  u64 bits = s.total * 8;
  u8 pad = 0x80;
  sha_update(s, &pad, 1);
  u8 zero = 0;
  while (s.fill != 56) sha_update(s, &zero, 1);
  u8 lenb[8];
  for (int i = 0; i < 8; i++) lenb[i] = (u8)(bits >> (56 - 8 * i));
  sha_update(s, lenb, 8);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = (u8)(s.h[i] >> 24);
    out[4 * i + 1] = (u8)(s.h[i] >> 16);
    out[4 * i + 2] = (u8)(s.h[i] >> 8);
    out[4 * i + 3] = (u8)s.h[i];
  }
}

static void sha256(const u8 *data, size_t len, u8 out[32]) {
  Sha256 s;
  sha_init(s);
  sha_update(s, data, len);
  sha_final(s, out);
}

static void hmac_sha256(const u8 *key, size_t keylen, const u8 *m1,
                        size_t l1, const u8 *m2, size_t l2, const u8 *m3,
                        size_t l3, u8 out[32]) {
  u8 k[64];
  memset(k, 0, 64);
  if (keylen > 64) {
    sha256(key, keylen, k);
  } else {
    memcpy(k, key, keylen);
  }
  u8 ipad[64], opad[64];
  for (int i = 0; i < 64; i++) {
    ipad[i] = k[i] ^ 0x36;
    opad[i] = k[i] ^ 0x5c;
  }
  Sha256 s;
  sha_init(s);
  sha_update(s, ipad, 64);
  if (l1) sha_update(s, m1, l1);
  if (l2) sha_update(s, m2, l2);
  if (l3) sha_update(s, m3, l3);
  u8 inner[32];
  sha_final(s, inner);
  sha_init(s);
  sha_update(s, opad, 64);
  sha_update(s, inner, 32);
  sha_final(s, out);
}

// RFC 6979 nonce (mirrors ecdsa.py:_rfc6979_k exactly)
static void rfc6979_k(u64 *k_out, const u8 priv[32], const u8 hash[32]) {
  u8 holder[32], key[32];
  memset(holder, 0x01, 32);
  memset(key, 0x00, 32);
  u8 sep0 = 0x00, sep1 = 0x01;
  // key = HMAC(key, holder || 0x00 || priv || hash)
  {
    u8 cat[32 + 1 + 32 + 32];
    memcpy(cat, holder, 32);
    cat[32] = sep0;
    memcpy(cat + 33, priv, 32);
    memcpy(cat + 65, hash, 32);
    hmac_sha256(key, 32, cat, sizeof(cat), nullptr, 0, nullptr, 0, key);
  }
  hmac_sha256(key, 32, holder, 32, nullptr, 0, nullptr, 0, holder);
  {
    u8 cat[32 + 1 + 32 + 32];
    memcpy(cat, holder, 32);
    cat[32] = sep1;
    memcpy(cat + 33, priv, 32);
    memcpy(cat + 65, hash, 32);
    hmac_sha256(key, 32, cat, sizeof(cat), nullptr, 0, nullptr, 0, key);
  }
  hmac_sha256(key, 32, holder, 32, nullptr, 0, nullptr, 0, holder);
  while (true) {
    hmac_sha256(key, 32, holder, 32, nullptr, 0, nullptr, 0, holder);
    u64 k[4];
    load_be(k, holder);
    // a candidate outside [1, n) comes with probability ~2^-128
    if (scalar_valid(k)) {
      memcpy(k_out, k, 32);
      return;
    }
    u8 cat[33];
    memcpy(cat, holder, 32);
    cat[32] = 0x00;
    hmac_sha256(key, 32, cat, 33, nullptr, 0, nullptr, 0, key);
    hmac_sha256(key, 32, holder, 32, nullptr, 0, nullptr, 0, holder);
  }
}

// z = hash mod n
static void hash_scalar(u64 z[4], const u8 hash[32]) {
  load_be(z, hash);
  if (cmp4(z, FN.m) >= 0) sub4(z, z, FN.m);
}

}  // namespace secp

// ---------------------------------------------------------------------------
// exported API
// ---------------------------------------------------------------------------

using namespace secp;

// bls381.cpp, the same library
extern "C" void lt_keccak256(const uint8_t *in, size_t inlen,
                             uint8_t out[32]);

extern "C" {

// returns 0 ok
int lt_ec_pubkey(const u8 priv[32], u8 out[33]) {
  u64 d[4];
  load_be(d, priv);
  if (!scalar_valid(d)) return 1;
  u64 ax[4], ay[4];
  ecmult_gen(ax, ay, d);
  out[0] = 0x02 | (u8)(ay[0] & 1);
  store_be(out + 1, ax);
  return 0;
}

// returns 0 ok; sig = r(32) || s(32) || v(1), low-s, recoverable
int lt_ec_sign(const u8 priv[32], const u8 hash[32], u8 sig[65]) {
  u64 d[4], z[4];
  load_be(d, priv);
  if (!scalar_valid(d)) return 1;
  hash_scalar(z, hash);
  u8 cur_hash[32];
  memcpy(cur_hash, hash, 32);
  int extra = 0;
  while (true) {
    u64 k[4];
    rfc6979_k(k, priv, cur_hash);
    u64 rx[4], ry[4];
    ecmult_gen(rx, ry, k);
    // r, the parity of y and whether x >= n are the signature's, public
    u64 r[4];
    memcpy(r, rx, 32);
    bool high_x = cmp4(r, FN.m) >= 0;
    if (high_x) sub4(r, r, FN.m);
    if (is_zero4(r)) goto retry;
    {
      // s = k^-1 (z + r d) mod n
      u64 km[4], kinv[4], rm[4], dm[4], zm[4], t[4], sm[4], s[4];
      to_mont(FN, km, k);
      fn_inv(kinv, km);
      to_mont(FN, rm, r);
      to_mont(FN, dm, d);
      to_mont(FN, zm, z);
      mont_mul(FN, t, rm, dm);
      mod_add(FN, t, t, zm);
      mont_mul(FN, sm, kinv, t);
      from_mont(FN, s, sm);
      if (is_zero4(s)) goto retry;
      u8 v = (u8)((ry[0] & 1) | (high_x ? 2 : 0));
      // low-s normalization (flips the parity bit)
      if (cmp4(s, HALF_N) > 0) {
        sub4(s, FN.m, s);
        v ^= 1;
      }
      store_be(sig, r);
      store_be(sig + 32, s);
      sig[64] = v;
      return 0;
    }
  retry:
    // mirror python: new nonce stream from sha256(orig_hash + extras)
    extra += 1;
    {
      u8 buf[32 + 16];
      memcpy(buf, hash, 32);
      for (int i = 0; i < extra && i < 16; i++) buf[32 + i] = 0;
      sha256(buf, 32 + (size_t)(extra < 16 ? extra : 16), cur_hash);
    }
  }
}

// returns 1 valid, 0 invalid
int lt_ec_verify(const u8 pub[33], const u8 hash[32], const u8 *sig,
                 size_t siglen) {
  if (siglen != 65) return 0;
  if (pub[0] != 2 && pub[0] != 3) return 0;
  u64 qx[4];
  load_be(qx, pub + 1);
  Ge q;
  if (!ge_decompress(q, qx, pub[0] & 1)) return 0;
  u64 r[4], s[4], z[4];
  load_be(r, sig);
  load_be(s, sig + 32);
  if (is_zero4(r) || is_zero4(s)) return 0;
  if (cmp4(r, FN.m) >= 0 || cmp4(s, FN.m) >= 0) return 0;
  hash_scalar(z, hash);
  // u1 = z / s, u2 = r / s
  u64 sinv[4], sm[4], u1[4], u2[4];
  fn_inv_var(sinv, s);
  to_mont(FN, sm, sinv);
  mont_mul(FN, u1, z, sm);  // plain * Montgomery = plain
  mont_mul(FN, u2, r, sm);
  Gej sum;
  ecmult(sum, q, u2, u1);
  if (sum.inf) return 0;
  // affine x mod n == r, i.e. x is r or r + n (x < p < 2n): X == x * Z^2
  Fe zz, fr, t;
  u64 rn[4];
  fe_sqr(zz, sum.z);
  fe_from4(fr, r);
  fe_mul(t, fr, zz);
  fe_sub(t, t, sum.x);
  if (fe_zero_mask(t)) return 1;
  if (add4(rn, r, FN.m) || cmp4(rn, FP_M) >= 0) return 0;
  fe_from4(fr, rn);
  fe_mul(t, fr, zz);
  fe_sub(t, t, sum.x);
  return fe_zero_mask(t) ? 1 : 0;
}

// the recovery itself, shared by the key and the address entries: the
// signer's affine point (plain limbs), false if the signature is invalid
static bool recover_affine(u64 ax[4], u64 ay[4], const u8 hash[32],
                           const u8 sig[65]) {
  u64 r[4], s[4];
  load_be(r, sig);
  load_be(s, sig + 32);
  u8 v = sig[64];
  if (v > 3) return false;
  if (is_zero4(r) || is_zero4(s)) return false;
  if (cmp4(r, FN.m) >= 0 || cmp4(s, FN.m) >= 0) return false;
  // x = r + (v & 2 ? n : 0)
  u64 x[4];
  memcpy(x, r, 32);
  if (v & 2) {
    if (add4(x, x, FN.m)) return false;  // overflow past 2^256
  }
  Ge rp;
  if (!ge_decompress(rp, x, v & 1)) return false;
  u64 z[4];
  hash_scalar(z, hash);
  // q = r^-1 (s R - z G) = (s/r) R + (-z/r) G
  u64 rinv[4], rm[4], nz[4], u1[4], u2[4];
  fn_inv_var(rinv, r);
  to_mont(FN, rm, rinv);
  sub4(nz, FN.m, z);
  if (is_zero4(z)) memset(nz, 0, 32);
  mont_mul(FN, u1, s, rm);  // plain * Montgomery = plain
  mont_mul(FN, u2, nz, rm);
  Gej q;
  ecmult(q, rp, u1, u2);
  return gej_affine(ax, ay, q);
}

// returns 0 ok; out = compressed recovered pubkey
int lt_ec_recover(const u8 hash[32], const u8 *sig, size_t siglen,
                  u8 out[33]) {
  if (siglen != 65) return 1;
  u64 ax[4], ay[4];
  if (!recover_affine(ax, ay, hash, sig)) return 1;
  out[0] = 0x02 | (u8)(ay[0] & 1);
  store_be(out + 1, ax);
  return 0;
}

// ---------------------------------------------------------------------------
// threaded batch ingest (role of the reference's background
// TransactionVerifier pool, Blockchain/Operations/TransactionVerifier.cs)
// ---------------------------------------------------------------------------

// shared thread-pool driver for the batch entries: build the G tables once
// (call_once inside, but building before spawn avoids serializing the
// workers), clamp nthreads to [1, min(n, hw)], chunk, run, join
static void run_threaded(size_t n, int nthreads,
                         const std::function<void(size_t, size_t)> &work) {
  ensure_tables();
  if (nthreads < 1) nthreads = 1;
  if ((size_t)nthreads > n) nthreads = (int)n;
  // read once: the call reads a file, ~75 us on the chip's host, more
  // than a recovery, and a batch of one pays it as a batch of 700 does
  static const unsigned hw = std::thread::hardware_concurrency();
  if (hw && (unsigned)nthreads > hw) nthreads = (int)hw;
  if (nthreads == 1) {
    work((size_t)0, n);
    return;
  }
  std::vector<std::thread> ts;
  size_t per = (n + nthreads - 1) / nthreads;
  for (int t = 0; t < nthreads; t++) {
    size_t lo = per * (size_t)t;
    size_t hi = lo + per < n ? lo + per : n;
    if (lo >= hi) break;
    ts.emplace_back(work, lo, hi);
  }
  for (auto &th : ts) th.join();
}

// hashes: n x 32; sigs: n x 65; outs: n x 33; oks: n x 1 (1 = recovered)
int lt_ec_recover_batch(const u8 *hashes, const u8 *sigs, size_t n,
                        int nthreads, u8 *outs, u8 *oks) {
  if (!n) return 0;
  run_threaded(n, nthreads, [&](size_t lo, size_t hi) {
    for (size_t i = lo; i < hi; i++) {
      oks[i] = lt_ec_recover(hashes + 32 * i, sigs + 65 * i, 65,
                             outs + 33 * i) == 0
                   ? 1
                   : 0;
    }
  });
  return 0;
}

// hashes: n x 32; sigs: n x 65; outs: n x 20; oks: n x 1 (1 = recovered).
// The signer's address, keccak256(x || y)[12:], taken from the affine
// point the recovery already holds: a caller that wants the sender never
// sees a compressed key, so nobody decompresses one (a modular square
// root) to hash it
int lt_ec_recover_address_batch(const u8 *hashes, const u8 *sigs, size_t n,
                                int nthreads, u8 *outs, u8 *oks) {
  if (!n) return 0;
  run_threaded(n, nthreads, [&](size_t lo, size_t hi) {
    for (size_t i = lo; i < hi; i++) {
      u64 ax[4], ay[4];
      u8 xy[64], digest[32];
      oks[i] = recover_affine(ax, ay, hashes + 32 * i, sigs + 65 * i) ? 1 : 0;
      if (!oks[i]) continue;
      store_be(xy, ax);
      store_be(xy + 32, ay);
      lt_keccak256(xy, 64, digest);
      memcpy(outs + 20 * i, digest + 12, 20);
    }
  });
  return 0;
}

// pubs: n x 33; hashes: n x 32; sigs: n x 65; oks: n x 1 (1 = valid)
int lt_ec_verify_batch(const u8 *pubs, const u8 *hashes, const u8 *sigs,
                       size_t n, int nthreads, u8 *oks) {
  if (!n) return 0;
  run_threaded(n, nthreads, [&](size_t lo, size_t hi) {
    for (size_t i = lo; i < hi; i++) {
      oks[i] = (u8)lt_ec_verify(pubs + 33 * i, hashes + 32 * i,
                                sigs + 65 * i, 65);
    }
  });
  return 0;
}

}  // extern "C"
