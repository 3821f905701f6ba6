/* BLS12-381 native runtime: Montgomery Fp, the Fp2/Fp6/Fp12 tower, G1/G2
 * Jacobian arithmetic, Pippenger MSM, and the optimal ate pairing.
 *
 * This is the framework's host-native crypto core — the slot the reference
 * fills with the milagro/arkworks C/Rust extensions behind its backend
 * switch (reference: tests/core/pyspec/eth2spec/utils/bls.py:224-296).
 * The tower layout and the pairing structure mirror the first-party Python
 * oracle (crypto/fields.py, crypto/pairing.py): u^2 = -1, v^3 = 1+u,
 * w^2 = v, generic affine line functions over the untwisted Fp12 image,
 * negative-x conjugation, naive hard-part exponentiation. The Python side
 * stays the oracle; tests cross-check every exported function against it.
 *
 * All byte interfaces are big-endian 48-byte field elements (matching the
 * SSZ/IETF compressed-point serialization the Python layer handles);
 * scalars are 32-byte big-endian. Infinity travels as a separate flag.
 *
 * Build: cc -O2 -fPIC -shared -o _bls12_381.so bls12_381.c
 */

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#include "bls12_381_consts.h"

typedef unsigned __int128 u128;

/* ---------------------------------------------------------------- Fp --- */

typedef struct { uint64_t l[6]; } fp;

static const fp FP_ZERO = {{0, 0, 0, 0, 0, 0}};

static int fp_is_zero(const fp *a) {
    uint64_t r = 0;
    for (int i = 0; i < 6; i++) r |= a->l[i];
    return r == 0;
}

static int fp_eq(const fp *a, const fp *b) {
    uint64_t r = 0;
    for (int i = 0; i < 6; i++) r |= a->l[i] ^ b->l[i];
    return r == 0;
}

/* -1 if a < b, 0 if equal, 1 if a > b (plain limb compare) */
static int limbs_cmp(const uint64_t *a, const uint64_t *b, int n) {
    for (int i = n - 1; i >= 0; i--) {
        if (a[i] < b[i]) return -1;
        if (a[i] > b[i]) return 1;
    }
    return 0;
}

static void fp_add(fp *r, const fp *a, const fp *b) {
    uint64_t t[6];
    u128 c = 0;
    for (int i = 0; i < 6; i++) {
        c += (u128)a->l[i] + b->l[i];
        t[i] = (uint64_t)c;
        c >>= 64;
    }
    if (c || limbs_cmp(t, FP_P, 6) >= 0) {
        u128 br = 0;
        for (int i = 0; i < 6; i++) {
            u128 d = (u128)t[i] - FP_P[i] - br;
            r->l[i] = (uint64_t)d;
            br = (d >> 64) & 1;
        }
    } else {
        memcpy(r->l, t, sizeof t);
    }
}

static void fp_sub(fp *r, const fp *a, const fp *b) {
    u128 br = 0;
    uint64_t t[6];
    for (int i = 0; i < 6; i++) {
        u128 d = (u128)a->l[i] - b->l[i] - br;
        t[i] = (uint64_t)d;
        br = (d >> 64) & 1;
    }
    if (br) {
        u128 c = 0;
        for (int i = 0; i < 6; i++) {
            c += (u128)t[i] + FP_P[i];
            r->l[i] = (uint64_t)c;
            c >>= 64;
        }
    } else {
        memcpy(r->l, t, sizeof t);
    }
}

static void fp_neg(fp *r, const fp *a) {
    if (fp_is_zero(a)) { *r = *a; return; }
    u128 br = 0;
    for (int i = 0; i < 6; i++) {
        u128 d = (u128)FP_P[i] - a->l[i] - br;
        r->l[i] = (uint64_t)d;
        br = (d >> 64) & 1;
    }
}

/* CIOS Montgomery multiplication: r = a*b*2^-384 mod p. */
#if defined(__x86_64__) && defined(__BMI2__) && defined(__ADX__)
/* CIOS Montgomery multiplication on mulx/adcx/adox dual carry chains —
 * ~1.5x the portable u128 version on the same core (the whole pairing /
 * hash-to-curve / decompression stack is fp_mul-bound, so this is a
 * framework-wide host-crypto speedup).  Bounds: inputs < p, so every
 * ai*b[5] high word is < 2^62 (p's top limb is 0x1a01...) and the t6
 * accumulator never overflows; at each row boundary t < 2p, so the
 * final carry out of the shifted add chain is provably zero.  The
 * loader proves CPU support at runtime (crash-isolated selftest probe,
 * native/__init__.py) before this build is accepted. */
#include <immintrin.h>
typedef unsigned long long ull_;
static void fp_mul(fp *r, const fp *a, const fp *b) {
    ull_ t0 = 0, t1 = 0, t2 = 0, t3 = 0, t4 = 0, t5 = 0, t6 = 0;
    const uint64_t *bl = b->l, *pl = FP_P;
    for (int i = 0; i < 6; i++) {
        ull_ ai = a->l[i], lo0, lo1, lo2, lo3, lo4, lo5, h0, h1, h2, h3, h4, h5;
        unsigned char c;
        lo0 = _mulx_u64(ai, bl[0], &h0); lo1 = _mulx_u64(ai, bl[1], &h1);
        lo2 = _mulx_u64(ai, bl[2], &h2); lo3 = _mulx_u64(ai, bl[3], &h3);
        lo4 = _mulx_u64(ai, bl[4], &h4); lo5 = _mulx_u64(ai, bl[5], &h5);
        c = _addcarryx_u64(0, t0, lo0, &t0); c = _addcarryx_u64(c, t1, lo1, &t1);
        c = _addcarryx_u64(c, t2, lo2, &t2); c = _addcarryx_u64(c, t3, lo3, &t3);
        c = _addcarryx_u64(c, t4, lo4, &t4); c = _addcarryx_u64(c, t5, lo5, &t5);
        t6 = (ull_)c;
        c = _addcarryx_u64(0, t1, h0, &t1); c = _addcarryx_u64(c, t2, h1, &t2);
        c = _addcarryx_u64(c, t3, h2, &t3); c = _addcarryx_u64(c, t4, h3, &t4);
        c = _addcarryx_u64(c, t5, h4, &t5); t6 += (ull_)c + h5;
        ull_ m = t0 * FP_N0;
        lo0 = _mulx_u64(m, pl[0], &h0); lo1 = _mulx_u64(m, pl[1], &h1);
        lo2 = _mulx_u64(m, pl[2], &h2); lo3 = _mulx_u64(m, pl[3], &h3);
        lo4 = _mulx_u64(m, pl[4], &h4); lo5 = _mulx_u64(m, pl[5], &h5);
        c = _addcarryx_u64(0, t0, lo0, &t0); c = _addcarryx_u64(c, t1, lo1, &t1);
        c = _addcarryx_u64(c, t2, lo2, &t2); c = _addcarryx_u64(c, t3, lo3, &t3);
        c = _addcarryx_u64(c, t4, lo4, &t4); c = _addcarryx_u64(c, t5, lo5, &t5);
        ull_ d1 = (ull_)c; /* carry into position 6 */
        c = _addcarryx_u64(0, t1, h0, &t0); c = _addcarryx_u64(c, t2, h1, &t1);
        c = _addcarryx_u64(c, t3, h2, &t2); c = _addcarryx_u64(c, t4, h3, &t3);
        c = _addcarryx_u64(c, t5, h4, &t4); c = _addcarryx_u64(c, t6, h5 + d1, &t5);
        t6 = 0; /* c provably 0: row boundary value < 2p */
    }
    ull_ o0, o1, o2, o3, o4, o5;
    unsigned char br;
    br = _subborrow_u64(0, t0, pl[0], &o0); br = _subborrow_u64(br, t1, pl[1], &o1);
    br = _subborrow_u64(br, t2, pl[2], &o2); br = _subborrow_u64(br, t3, pl[3], &o3);
    br = _subborrow_u64(br, t4, pl[4], &o4); br = _subborrow_u64(br, t5, pl[5], &o5);
    if (!br) { t0 = o0; t1 = o1; t2 = o2; t3 = o3; t4 = o4; t5 = o5; }
    r->l[0] = t0; r->l[1] = t1; r->l[2] = t2;
    r->l[3] = t3; r->l[4] = t4; r->l[5] = t5;
}
#else
static void fp_mul(fp *r, const fp *a, const fp *b) {
    uint64_t t[8];
    memset(t, 0, sizeof t);
    for (int i = 0; i < 6; i++) {
        u128 c = 0;
        for (int j = 0; j < 6; j++) {
            c += (u128)a->l[i] * b->l[j] + t[j];
            t[j] = (uint64_t)c;
            c >>= 64;
        }
        c += t[6];
        t[6] = (uint64_t)c;
        t[7] = (uint64_t)(c >> 64);

        uint64_t m = t[0] * FP_N0;
        c = (u128)m * FP_P[0] + t[0];
        c >>= 64;
        for (int j = 1; j < 6; j++) {
            c += (u128)m * FP_P[j] + t[j];
            t[j - 1] = (uint64_t)c;
            c >>= 64;
        }
        c += t[6];
        t[5] = (uint64_t)c;
        t[6] = t[7] + (uint64_t)(c >> 64);
        t[7] = 0;
    }
    if (t[6] || limbs_cmp(t, FP_P, 6) >= 0) {
        u128 br = 0;
        for (int i = 0; i < 6; i++) {
            u128 d = (u128)t[i] - FP_P[i] - br;
            r->l[i] = (uint64_t)d;
            br = (d >> 64) & 1;
        }
    } else {
        memcpy(r->l, t, 6 * sizeof(uint64_t));
    }
}
#endif /* BMI2+ADX vs portable fp_mul */

static void fp_sqr(fp *r, const fp *a) { fp_mul(r, a, a); }

static void fp_one(fp *r) { memcpy(r->l, FP_R1, sizeof r->l); }

static void fp_from_plain(fp *r, const uint64_t plain[6]) {
    fp tmp, r2;
    memcpy(tmp.l, plain, sizeof tmp.l);
    memcpy(r2.l, FP_R2, sizeof r2.l);
    fp_mul(r, &tmp, &r2);
}

static void fp_to_plain(uint64_t out[6], const fp *a) {
    fp one_plain = {{1, 0, 0, 0, 0, 0}};
    fp t;
    fp_mul(&t, a, &one_plain);
    memcpy(out, t.l, sizeof t.l);
}

static void fp_from_be(fp *r, const uint8_t in[48]) {
    uint64_t plain[6];
    for (int i = 0; i < 6; i++) {
        uint64_t v = 0;
        const uint8_t *p = in + (5 - i) * 8;
        for (int j = 0; j < 8; j++) v = (v << 8) | p[j];
        plain[i] = v;
    }
    fp_from_plain(r, plain);
}

static void fp_to_be(uint8_t out[48], const fp *a) {
    uint64_t plain[6];
    fp_to_plain(plain, a);
    for (int i = 0; i < 6; i++) {
        uint64_t v = plain[i];
        uint8_t *p = out + (5 - i) * 8;
        for (int j = 7; j >= 0; j--) { p[j] = (uint8_t)v; v >>= 8; }
    }
}

/* MSB-first square-and-multiply over a little-endian limb exponent. */
static void fp_pow_limbs(fp *r, const fp *base, const uint64_t *exp, int nlimbs) {
    int top = -1;
    for (int i = nlimbs - 1; i >= 0 && top < 0; i--)
        if (exp[i]) {
            for (int b = 63; b >= 0; b--)
                if ((exp[i] >> b) & 1) { top = i * 64 + b; break; }
        }
    fp acc;
    fp_one(&acc);
    if (top < 0) { *r = acc; return; }
    for (int bit = top; bit >= 0; bit--) {
        fp_sqr(&acc, &acc);
        if ((exp[bit / 64] >> (bit % 64)) & 1) fp_mul(&acc, &acc, base);
    }
    *r = acc;
}

/* plain-limb helpers for the binary extended GCD */

static int limbs_is_even(const uint64_t a[6]) { return (a[0] & 1) == 0; }

static int limbs_is_one(const uint64_t a[6]) {
    return a[0] == 1 && !(a[1] | a[2] | a[3] | a[4] | a[5]);
}

static int limbs_is_zero6(const uint64_t a[6]) {
    return !(a[0] | a[1] | a[2] | a[3] | a[4] | a[5]);
}

static void limbs_sub6(uint64_t r[6], const uint64_t a[6], const uint64_t b[6]) {
    u128 br = 0;
    for (int i = 0; i < 6; i++) {
        u128 d = (u128)a[i] - b[i] - br;
        r[i] = (uint64_t)d;
        br = (d >> 64) & 1;
    }
}

/* r = a >> 1, with an incoming top carry bit */
static void limbs_shr1(uint64_t r[6], const uint64_t a[6], uint64_t carry) {
    for (int i = 0; i < 6; i++) {
        uint64_t next = (i < 5) ? a[i + 1] : carry;
        r[i] = (a[i] >> 1) | (next << 63);
    }
}

/* halve x modulo p: x even -> x>>1, else (x+p)>>1 (needs the carry bit) */
static void limbs_half_mod_p(uint64_t x[6]) {
    if (limbs_is_even(x)) {
        limbs_shr1(x, x, 0);
    } else {
        u128 c = 0;
        uint64_t t[6];
        for (int i = 0; i < 6; i++) {
            c += (u128)x[i] + FP_P[i];
            t[i] = (uint64_t)c;
            c >>= 64;
        }
        limbs_shr1(x, t, (uint64_t)c);
    }
}

static void limbs_submod_p(uint64_t r[6], const uint64_t a[6], const uint64_t b[6]) {
    u128 br = 0;
    uint64_t t[6];
    for (int i = 0; i < 6; i++) {
        u128 d = (u128)a[i] - b[i] - br;
        t[i] = (uint64_t)d;
        br = (d >> 64) & 1;
    }
    if (br) {
        u128 c = 0;
        for (int i = 0; i < 6; i++) {
            c += (u128)t[i] + FP_P[i];
            r[i] = (uint64_t)c;
            c >>= 64;
        }
    } else {
        memcpy(r, t, 6 * sizeof(uint64_t));
    }
}

/* Binary extended GCD inversion (odd modulus): ~100x faster than the
 * Fermat pow and the reason the Miller loop's affine formulation is viable
 * on the host.  Falls back to pow for zero input (returns zero like pow). */
static void fp_inv(fp *r, const fp *a) {
    uint64_t u[6], v[6], x1[6], x2[6];
    fp_to_plain(u, a);
    if (limbs_is_zero6(u)) { *r = FP_ZERO; return; }
    memcpy(v, FP_P, sizeof v);
    memset(x1, 0, sizeof x1);
    x1[0] = 1;
    memset(x2, 0, sizeof x2);
    while (!limbs_is_one(u) && !limbs_is_one(v)) {
        while (limbs_is_even(u)) {
            limbs_shr1(u, u, 0);
            limbs_half_mod_p(x1);
        }
        while (limbs_is_even(v)) {
            limbs_shr1(v, v, 0);
            limbs_half_mod_p(x2);
        }
        if (limbs_cmp(u, v, 6) >= 0) {
            limbs_sub6(u, u, v);
            limbs_submod_p(x1, x1, x2);
        } else {
            limbs_sub6(v, v, u);
            limbs_submod_p(x2, x2, x1);
        }
    }
    fp_from_plain(r, limbs_is_one(u) ? x1 : x2);
}

/* sqrt for p = 3 mod 4; returns 1 on success. */
static int fp_sqrt(fp *r, const fp *a) {
    fp c, c2;
    fp_pow_limbs(&c, a, FP_SQRT_EXP, 6);
    fp_sqr(&c2, &c);
    if (!fp_eq(&c2, a)) return 0;
    *r = c;
    return 1;
}

/* --------------------------------------------------------------- Fp2 --- */

typedef struct { fp c0, c1; } fp2;

static void fp2_zero(fp2 *r) { r->c0 = FP_ZERO; r->c1 = FP_ZERO; }
static void fp2_one(fp2 *r) { fp_one(&r->c0); r->c1 = FP_ZERO; }

static int fp2_is_zero(const fp2 *a) { return fp_is_zero(&a->c0) && fp_is_zero(&a->c1); }
static int fp2_eq(const fp2 *a, const fp2 *b) { return fp_eq(&a->c0, &b->c0) && fp_eq(&a->c1, &b->c1); }

static void fp2_add(fp2 *r, const fp2 *a, const fp2 *b) {
    fp_add(&r->c0, &a->c0, &b->c0);
    fp_add(&r->c1, &a->c1, &b->c1);
}

static void fp2_sub(fp2 *r, const fp2 *a, const fp2 *b) {
    fp_sub(&r->c0, &a->c0, &b->c0);
    fp_sub(&r->c1, &a->c1, &b->c1);
}

static void fp2_neg(fp2 *r, const fp2 *a) {
    fp_neg(&r->c0, &a->c0);
    fp_neg(&r->c1, &a->c1);
}

static void fp2_conj(fp2 *r, const fp2 *a) {
    r->c0 = a->c0;
    fp_neg(&r->c1, &a->c1);
}

static void fp2_mul(fp2 *r, const fp2 *a, const fp2 *b) {
    fp t0, t1, s0, s1, cross;
    fp_mul(&t0, &a->c0, &b->c0);
    fp_mul(&t1, &a->c1, &b->c1);
    fp_add(&s0, &a->c0, &a->c1);
    fp_add(&s1, &b->c0, &b->c1);
    fp_mul(&cross, &s0, &s1);
    fp_sub(&cross, &cross, &t0);
    fp_sub(&cross, &cross, &t1);
    fp_sub(&r->c0, &t0, &t1);
    r->c1 = cross;
}

static void fp2_sqr(fp2 *r, const fp2 *a) {
    fp s, d, m;
    fp_add(&s, &a->c0, &a->c1);
    fp_sub(&d, &a->c0, &a->c1);
    fp_mul(&m, &a->c0, &a->c1);
    fp_mul(&r->c0, &s, &d);
    fp_add(&r->c1, &m, &m);
}

static void fp2_mul_fp(fp2 *r, const fp2 *a, const fp *k) {
    fp_mul(&r->c0, &a->c0, k);
    fp_mul(&r->c1, &a->c1, k);
}

/* multiply by xi = 1 + u: (c0 - c1) + (c0 + c1) u */
static void fp2_mul_xi(fp2 *r, const fp2 *a) {
    fp t0, t1;
    fp_sub(&t0, &a->c0, &a->c1);
    fp_add(&t1, &a->c0, &a->c1);
    r->c0 = t0;
    r->c1 = t1;
}

static void fp2_inv(fp2 *r, const fp2 *a) {
    fp n, t, ninv;
    fp_sqr(&n, &a->c0);
    fp_sqr(&t, &a->c1);
    fp_add(&n, &n, &t);
    fp_inv(&ninv, &n);
    fp_mul(&r->c0, &a->c0, &ninv);
    fp_mul(&t, &a->c1, &ninv);
    fp_neg(&r->c1, &t);
}

/* sqrt in Fp2 by the norm method (mirrors crypto/fields.py Fq2.sqrt). */
static int fp2_sqrt(fp2 *r, const fp2 *a) {
    if (fp2_is_zero(a)) { fp2_zero(r); return 1; }
    if (fp_is_zero(&a->c1)) {
        fp s;
        if (fp_sqrt(&s, &a->c0)) { r->c0 = s; r->c1 = FP_ZERO; return 1; }
        fp na;
        fp_neg(&na, &a->c0);
        if (!fp_sqrt(&s, &na)) return 0;
        r->c0 = FP_ZERO;
        r->c1 = s;
        return 1;
    }
    fp norm, t, sn;
    fp_sqr(&norm, &a->c0);
    fp_sqr(&t, &a->c1);
    fp_add(&norm, &norm, &t);
    if (!fp_sqrt(&sn, &norm)) return 0;
    fp two, inv2;
    fp_one(&two);
    fp_add(&two, &two, &two);
    fp_inv(&inv2, &two);
    for (int attempt = 0; attempt < 2; attempt++) {
        fp half, x;
        if (attempt == 0) fp_add(&half, &a->c0, &sn);
        else fp_sub(&half, &a->c0, &sn);
        fp_mul(&half, &half, &inv2);
        if (!fp_sqrt(&x, &half) || fp_is_zero(&x)) continue;
        fp twox, txinv, y;
        fp_add(&twox, &x, &x);
        fp_inv(&txinv, &twox);
        fp_mul(&y, &a->c1, &txinv);
        fp2 cand = { x, y }, sq;
        fp2_sqr(&sq, &cand);
        if (fp2_eq(&sq, a)) { *r = cand; return 1; }
    }
    return 0;
}

/* --------------------------------------------------------------- Fp6 --- */

typedef struct { fp2 c0, c1, c2; } fp6;

static void fp6_zero(fp6 *r) { fp2_zero(&r->c0); fp2_zero(&r->c1); fp2_zero(&r->c2); }
static void fp6_one(fp6 *r) { fp2_one(&r->c0); fp2_zero(&r->c1); fp2_zero(&r->c2); }

static int fp6_is_zero(const fp6 *a) {
    return fp2_is_zero(&a->c0) && fp2_is_zero(&a->c1) && fp2_is_zero(&a->c2);
}

static int fp6_eq(const fp6 *a, const fp6 *b) {
    return fp2_eq(&a->c0, &b->c0) && fp2_eq(&a->c1, &b->c1) && fp2_eq(&a->c2, &b->c2);
}

static void fp6_add(fp6 *r, const fp6 *a, const fp6 *b) {
    fp2_add(&r->c0, &a->c0, &b->c0);
    fp2_add(&r->c1, &a->c1, &b->c1);
    fp2_add(&r->c2, &a->c2, &b->c2);
}

static void fp6_sub(fp6 *r, const fp6 *a, const fp6 *b) {
    fp2_sub(&r->c0, &a->c0, &b->c0);
    fp2_sub(&r->c1, &a->c1, &b->c1);
    fp2_sub(&r->c2, &a->c2, &b->c2);
}

static void fp6_neg(fp6 *r, const fp6 *a) {
    fp2_neg(&r->c0, &a->c0);
    fp2_neg(&r->c1, &a->c1);
    fp2_neg(&r->c2, &a->c2);
}

static void fp6_mul(fp6 *r, const fp6 *a, const fp6 *b) {
    fp2 t0, t1, t2, s, u, v;
    fp2_mul(&t0, &a->c0, &b->c0);
    fp2_mul(&t1, &a->c1, &b->c1);
    fp2_mul(&t2, &a->c2, &b->c2);

    /* c0 = t0 + xi*((a1+a2)(b1+b2) - t1 - t2) */
    fp2_add(&s, &a->c1, &a->c2);
    fp2_add(&u, &b->c1, &b->c2);
    fp2_mul(&v, &s, &u);
    fp2_sub(&v, &v, &t1);
    fp2_sub(&v, &v, &t2);
    fp2_mul_xi(&v, &v);
    fp2 c0, c1, c2;
    fp2_add(&c0, &t0, &v);

    /* c1 = (a0+a1)(b0+b1) - t0 - t1 + xi*t2 */
    fp2_add(&s, &a->c0, &a->c1);
    fp2_add(&u, &b->c0, &b->c1);
    fp2_mul(&v, &s, &u);
    fp2_sub(&v, &v, &t0);
    fp2_sub(&v, &v, &t1);
    fp2 xt2;
    fp2_mul_xi(&xt2, &t2);
    fp2_add(&c1, &v, &xt2);

    /* c2 = (a0+a2)(b0+b2) - t0 - t2 + t1 */
    fp2_add(&s, &a->c0, &a->c2);
    fp2_add(&u, &b->c0, &b->c2);
    fp2_mul(&v, &s, &u);
    fp2_sub(&v, &v, &t0);
    fp2_sub(&v, &v, &t2);
    fp2_add(&c2, &v, &t1);

    r->c0 = c0; r->c1 = c1; r->c2 = c2;
}

/* CH-SQR2 squaring: 5 fp2 multiplications instead of 6. */
static void fp6_sqr(fp6 *r, const fp6 *a) {
    fp2 s0, s1, s2, s3, s4, t;
    fp2_sqr(&s0, &a->c0);
    fp2_mul(&s1, &a->c0, &a->c1);
    fp2_add(&s1, &s1, &s1);
    fp2_sub(&t, &a->c0, &a->c1);
    fp2_add(&t, &t, &a->c2);
    fp2_sqr(&s2, &t);
    fp2_mul(&s3, &a->c1, &a->c2);
    fp2_add(&s3, &s3, &s3);
    fp2_sqr(&s4, &a->c2);
    fp2 c0, c1, c2;
    fp2_mul_xi(&t, &s3);
    fp2_add(&c0, &s0, &t);
    fp2_mul_xi(&t, &s4);
    fp2_add(&c1, &s1, &t);
    fp2_add(&c2, &s1, &s2);
    fp2_add(&c2, &c2, &s3);
    fp2_sub(&c2, &c2, &s0);
    fp2_sub(&c2, &c2, &s4);
    r->c0 = c0; r->c1 = c1; r->c2 = c2;
}

/* multiply by v: (c0,c1,c2) -> (xi*c2, c0, c1) */
static void fp6_mul_v(fp6 *r, const fp6 *a) {
    fp2 t;
    fp2_mul_xi(&t, &a->c2);
    fp2 c1 = a->c0, c2 = a->c1;
    r->c0 = t;
    r->c1 = c1;
    r->c2 = c2;
}

static void fp6_inv(fp6 *r, const fp6 *a) {
    fp2 t0, t1, t2, s, v, denom;
    /* t0 = a0^2 - xi*a1*a2 */
    fp2_sqr(&t0, &a->c0);
    fp2_mul(&s, &a->c1, &a->c2);
    fp2_mul_xi(&s, &s);
    fp2_sub(&t0, &t0, &s);
    /* t1 = xi*a2^2 - a0*a1 */
    fp2_sqr(&t1, &a->c2);
    fp2_mul_xi(&t1, &t1);
    fp2_mul(&s, &a->c0, &a->c1);
    fp2_sub(&t1, &t1, &s);
    /* t2 = a1^2 - a0*a2 */
    fp2_sqr(&t2, &a->c1);
    fp2_mul(&s, &a->c0, &a->c2);
    fp2_sub(&t2, &t2, &s);
    /* denom = a0*t0 + xi*(a2*t1 + a1*t2) */
    fp2_mul(&s, &a->c2, &t1);
    fp2_mul(&v, &a->c1, &t2);
    fp2_add(&s, &s, &v);
    fp2_mul_xi(&s, &s);
    fp2_mul(&v, &a->c0, &t0);
    fp2_add(&s, &s, &v);
    fp2_inv(&denom, &s);
    fp2_mul(&r->c0, &t0, &denom);
    fp2_mul(&r->c1, &t1, &denom);
    fp2_mul(&r->c2, &t2, &denom);
}

/* -------------------------------------------------------------- Fp12 --- */

typedef struct { fp6 c0, c1; } fp12;

static void fp12_one(fp12 *r) { fp6_one(&r->c0); fp6_zero(&r->c1); }

static int fp12_eq(const fp12 *a, const fp12 *b) {
    return fp6_eq(&a->c0, &b->c0) && fp6_eq(&a->c1, &b->c1);
}

static int fp12_is_one(const fp12 *a) {
    fp12 one;
    fp12_one(&one);
    return fp12_eq(a, &one);
}

static void fp12_add(fp12 *r, const fp12 *a, const fp12 *b) {
    fp6_add(&r->c0, &a->c0, &b->c0);
    fp6_add(&r->c1, &a->c1, &b->c1);
}

static void fp12_sub(fp12 *r, const fp12 *a, const fp12 *b) {
    fp6_sub(&r->c0, &a->c0, &b->c0);
    fp6_sub(&r->c1, &a->c1, &b->c1);
}

static void fp12_mul(fp12 *r, const fp12 *a, const fp12 *b) {
    fp6 t0, t1, s0, s1, cross, shifted;
    fp6_mul(&t0, &a->c0, &b->c0);
    fp6_mul(&t1, &a->c1, &b->c1);
    fp6_add(&s0, &a->c0, &a->c1);
    fp6_add(&s1, &b->c0, &b->c1);
    fp6_mul(&cross, &s0, &s1);
    fp6_sub(&cross, &cross, &t0);
    fp6_sub(&cross, &cross, &t1);
    fp6_mul_v(&shifted, &t1);
    fp6_add(&r->c0, &t0, &shifted);
    r->c1 = cross;
}

/* (c0 + c1 w)^2 = (c0^2 + v c1^2) + 2 c0 c1 w, via Karatsuba:
 * c0' = (c0+c1)(c0+v*c1) - t - v*t,  c1' = 2t,  t = c0*c1. */
static void fp12_sqr(fp12 *r, const fp12 *a) {
    fp6 t, s0, s1, vt, c0;
    fp6_mul(&t, &a->c0, &a->c1);
    fp6_add(&s0, &a->c0, &a->c1);
    fp6_mul_v(&vt, &a->c1);
    fp6_add(&s1, &a->c0, &vt);
    fp6_mul(&c0, &s0, &s1);
    fp6_sub(&c0, &c0, &t);
    fp6_mul_v(&vt, &t);
    fp6_sub(&c0, &c0, &vt);
    r->c0 = c0;
    fp6_add(&r->c1, &t, &t);
}

static void fp12_conj(fp12 *r, const fp12 *a) {
    r->c0 = a->c0;
    fp6_neg(&r->c1, &a->c1);
}

static void fp12_inv(fp12 *r, const fp12 *a) {
    fp6 t0, t1, t;
    fp6_sqr(&t0, &a->c0);
    fp6_sqr(&t1, &a->c1);
    fp6_mul_v(&t1, &t1);
    fp6_sub(&t0, &t0, &t1);
    fp6_inv(&t, &t0);
    fp6_mul(&r->c0, &a->c0, &t);
    fp6_mul(&t1, &a->c1, &t);
    fp6_neg(&r->c1, &t1);
}

static void fp12_neg(fp12 *r, const fp12 *a) {
    fp6_neg(&r->c0, &a->c0);
    fp6_neg(&r->c1, &a->c1);
}

/* frobenius^2 via gamma powers on the flattened w^i coefficients
 * (coeff order: c0.c0, c1.c0, c0.c1, c1.c1, c0.c2, c1.c2). */
static fp FROB2_POWS[6]; /* gamma^i in Montgomery form, set in init */

static void fp12_frob2(fp12 *r, const fp12 *a) {
    fp2 *rc[6] = { &r->c0.c0, &r->c1.c0, &r->c0.c1, &r->c1.c1, &r->c0.c2, &r->c1.c2 };
    const fp2 *ac[6] = { &a->c0.c0, &a->c1.c0, &a->c0.c1, &a->c1.c1, &a->c0.c2, &a->c1.c2 };
    for (int i = 0; i < 6; i++) fp2_mul_fp(rc[i], ac[i], &FROB2_POWS[i]);
}

static void fp12_pow_limbs(fp12 *r, const fp12 *base, const uint64_t *exp, int nlimbs, int nbits) {
    fp12 acc;
    fp12_one(&acc);
    for (int bit = nbits - 1; bit >= 0; bit--) {
        fp12_sqr(&acc, &acc);
        if ((exp[bit / 64] >> (bit % 64)) & 1) fp12_mul(&acc, &acc, base);
    }
    *r = acc;
}

/* ------------------------------------------------------------- curves --- */

/* Jacobian points; Z == 0 encodes infinity. One implementation per
 * coordinate field (formulas identical to crypto/curve.py _jac_*). */

typedef struct { fp X, Y, Z; } g1p;
typedef struct { fp2 X, Y, Z; } g2p;

static void g1_set_inf(g1p *r) { r->X = FP_ZERO; fp_one(&r->Y); r->Z = FP_ZERO; }
static int g1_is_inf(const g1p *p) { return fp_is_zero(&p->Z); }
static void g2_set_inf(g2p *r) { fp2_zero(&r->X); fp2_one(&r->Y); fp2_zero(&r->Z); }
static int g2_is_inf(const g2p *p) { return fp2_is_zero(&p->Z); }

static void g1_dbl(g1p *r, const g1p *p) {
    if (g1_is_inf(p) || fp_is_zero(&p->Y)) { g1_set_inf(r); return; }
    fp A, B, C, D, E, F, t, X3, Y3, Z3;
    fp_sqr(&A, &p->X);
    fp_sqr(&B, &p->Y);
    fp_sqr(&C, &B);
    fp_add(&t, &p->X, &B);
    fp_sqr(&t, &t);
    fp_sub(&t, &t, &A);
    fp_sub(&D, &t, &C);
    fp_add(&D, &D, &D);
    fp_add(&E, &A, &A);
    fp_add(&E, &E, &A);
    fp_sqr(&F, &E);
    fp_sub(&X3, &F, &D);
    fp_sub(&X3, &X3, &D);
    fp eight_c;
    fp_add(&eight_c, &C, &C);
    fp_add(&eight_c, &eight_c, &eight_c);
    fp_add(&eight_c, &eight_c, &eight_c);
    fp_sub(&t, &D, &X3);
    fp_mul(&Y3, &E, &t);
    fp_sub(&Y3, &Y3, &eight_c);
    fp_mul(&Z3, &p->Y, &p->Z);
    fp_add(&Z3, &Z3, &Z3);
    r->X = X3; r->Y = Y3; r->Z = Z3;
}

static void g1_add(g1p *r, const g1p *p, const g1p *q) {
    if (g1_is_inf(p)) { *r = *q; return; }
    if (g1_is_inf(q)) { *r = *p; return; }
    fp Z1Z1, Z2Z2, U1, U2, S1, S2, t;
    fp_sqr(&Z1Z1, &p->Z);
    fp_sqr(&Z2Z2, &q->Z);
    fp_mul(&U1, &p->X, &Z2Z2);
    fp_mul(&U2, &q->X, &Z1Z1);
    fp_mul(&t, &p->Y, &q->Z);
    fp_mul(&S1, &t, &Z2Z2);
    fp_mul(&t, &q->Y, &p->Z);
    fp_mul(&S2, &t, &Z1Z1);
    if (fp_eq(&U1, &U2)) {
        if (fp_eq(&S1, &S2)) { g1_dbl(r, p); return; }
        g1_set_inf(r);
        return;
    }
    fp H, I, J, rr, V, X3, Y3, Z3;
    fp_sub(&H, &U2, &U1);
    fp_add(&I, &H, &H);
    fp_sqr(&I, &I);
    fp_mul(&J, &H, &I);
    fp_sub(&rr, &S2, &S1);
    fp_add(&rr, &rr, &rr);
    fp_mul(&V, &U1, &I);
    fp_sqr(&X3, &rr);
    fp_sub(&X3, &X3, &J);
    fp_sub(&X3, &X3, &V);
    fp_sub(&X3, &X3, &V);
    fp_sub(&t, &V, &X3);
    fp_mul(&Y3, &rr, &t);
    fp s1j;
    fp_mul(&s1j, &S1, &J);
    fp_add(&s1j, &s1j, &s1j);
    fp_sub(&Y3, &Y3, &s1j);
    fp_add(&Z3, &p->Z, &q->Z);
    fp_sqr(&Z3, &Z3);
    fp_sub(&Z3, &Z3, &Z1Z1);
    fp_sub(&Z3, &Z3, &Z2Z2);
    fp_mul(&Z3, &Z3, &H);
    r->X = X3; r->Y = Y3; r->Z = Z3;
}

static void g2_dbl(g2p *r, const g2p *p) {
    if (g2_is_inf(p) || fp2_is_zero(&p->Y)) { g2_set_inf(r); return; }
    fp2 A, B, C, D, E, F, t, X3, Y3, Z3;
    fp2_sqr(&A, &p->X);
    fp2_sqr(&B, &p->Y);
    fp2_sqr(&C, &B);
    fp2_add(&t, &p->X, &B);
    fp2_sqr(&t, &t);
    fp2_sub(&t, &t, &A);
    fp2_sub(&D, &t, &C);
    fp2_add(&D, &D, &D);
    fp2_add(&E, &A, &A);
    fp2_add(&E, &E, &A);
    fp2_sqr(&F, &E);
    fp2_sub(&X3, &F, &D);
    fp2_sub(&X3, &X3, &D);
    fp2 eight_c;
    fp2_add(&eight_c, &C, &C);
    fp2_add(&eight_c, &eight_c, &eight_c);
    fp2_add(&eight_c, &eight_c, &eight_c);
    fp2_sub(&t, &D, &X3);
    fp2_mul(&Y3, &E, &t);
    fp2_sub(&Y3, &Y3, &eight_c);
    fp2_mul(&Z3, &p->Y, &p->Z);
    fp2_add(&Z3, &Z3, &Z3);
    r->X = X3; r->Y = Y3; r->Z = Z3;
}

static void g2_add(g2p *r, const g2p *p, const g2p *q) {
    if (g2_is_inf(p)) { *r = *q; return; }
    if (g2_is_inf(q)) { *r = *p; return; }
    fp2 Z1Z1, Z2Z2, U1, U2, S1, S2, t;
    fp2_sqr(&Z1Z1, &p->Z);
    fp2_sqr(&Z2Z2, &q->Z);
    fp2_mul(&U1, &p->X, &Z2Z2);
    fp2_mul(&U2, &q->X, &Z1Z1);
    fp2_mul(&t, &p->Y, &q->Z);
    fp2_mul(&S1, &t, &Z2Z2);
    fp2_mul(&t, &q->Y, &p->Z);
    fp2_mul(&S2, &t, &Z1Z1);
    if (fp2_eq(&U1, &U2)) {
        if (fp2_eq(&S1, &S2)) { g2_dbl(r, p); return; }
        g2_set_inf(r);
        return;
    }
    fp2 H, I, J, rr, V, X3, Y3, Z3;
    fp2_sub(&H, &U2, &U1);
    fp2_add(&I, &H, &H);
    fp2_sqr(&I, &I);
    fp2_mul(&J, &H, &I);
    fp2_sub(&rr, &S2, &S1);
    fp2_add(&rr, &rr, &rr);
    fp2_mul(&V, &U1, &I);
    fp2_sqr(&X3, &rr);
    fp2_sub(&X3, &X3, &J);
    fp2_sub(&X3, &X3, &V);
    fp2_sub(&X3, &X3, &V);
    fp2_sub(&t, &V, &X3);
    fp2_mul(&Y3, &rr, &t);
    fp2 s1j;
    fp2_mul(&s1j, &S1, &J);
    fp2_add(&s1j, &s1j, &s1j);
    fp2_sub(&Y3, &Y3, &s1j);
    fp2_add(&Z3, &p->Z, &q->Z);
    fp2_sqr(&Z3, &Z3);
    fp2_sub(&Z3, &Z3, &Z1Z1);
    fp2_sub(&Z3, &Z3, &Z2Z2);
    fp2_mul(&Z3, &Z3, &H);
    r->X = X3; r->Y = Y3; r->Z = Z3;
}

static void g1_from_affine(g1p *r, const fp *x, const fp *y) {
    r->X = *x;
    r->Y = *y;
    fp_one(&r->Z);
}

static void g2_from_affine(g2p *r, const fp2 *x, const fp2 *y) {
    r->X = *x;
    r->Y = *y;
    fp2_one(&r->Z);
}

static void g1_to_affine(fp *x, fp *y, int *inf, const g1p *p) {
    if (g1_is_inf(p)) { *inf = 1; *x = FP_ZERO; *y = FP_ZERO; return; }
    *inf = 0;
    fp zi, zi2, zi3;
    fp_inv(&zi, &p->Z);
    fp_sqr(&zi2, &zi);
    fp_mul(&zi3, &zi2, &zi);
    fp_mul(x, &p->X, &zi2);
    fp_mul(y, &p->Y, &zi3);
}

static void g2_to_affine(fp2 *x, fp2 *y, int *inf, const g2p *p) {
    if (g2_is_inf(p)) { *inf = 1; fp2_zero(x); fp2_zero(y); return; }
    *inf = 0;
    fp2 zi, zi2, zi3;
    fp2_inv(&zi, &p->Z);
    fp2_sqr(&zi2, &zi);
    fp2_mul(&zi3, &zi2, &zi);
    fp2_mul(x, &p->X, &zi2);
    fp2_mul(y, &p->Y, &zi3);
}

/* 4-bit fixed-window scalar multiplication; scalar is 4 LE limbs (256 bit). */

static void g1_mul_scalar(g1p *r, const g1p *p, const uint64_t sc[4]) {
    g1p table[16];
    g1_set_inf(&table[0]);
    table[1] = *p;
    for (int i = 2; i < 16; i++) g1_add(&table[i], &table[i - 1], p);
    g1p acc;
    g1_set_inf(&acc);
    for (int nib = 63; nib >= 0; nib--) {
        for (int k = 0; k < 4; k++) g1_dbl(&acc, &acc);
        unsigned idx = (unsigned)((sc[nib / 16] >> ((nib % 16) * 4)) & 0xF);
        if (idx) g1_add(&acc, &acc, &table[idx]);
    }
    *r = acc;
}

static void g2_mul_scalar(g2p *r, const g2p *p, const uint64_t sc[4]) {
    g2p table[16];
    g2_set_inf(&table[0]);
    table[1] = *p;
    for (int i = 2; i < 16; i++) g2_add(&table[i], &table[i - 1], p);
    g2p acc;
    g2_set_inf(&acc);
    for (int nib = 63; nib >= 0; nib--) {
        for (int k = 0; k < 4; k++) g2_dbl(&acc, &acc);
        unsigned idx = (unsigned)((sc[nib / 16] >> ((nib % 16) * 4)) & 0xF);
        if (idx) g2_add(&acc, &acc, &table[idx]);
    }
    *r = acc;
}

/* arbitrary-length big-endian scalar multiplication (nibble windows) —
 * covers the 636-bit h_eff cofactor clearing of hash-to-G2. */
static void g1_mul_be(g1p *r, const g1p *p, const uint8_t *be, uint64_t len) {
    g1p table[16];
    g1_set_inf(&table[0]);
    table[1] = *p;
    for (int i = 2; i < 16; i++) g1_add(&table[i], &table[i - 1], p);
    g1p acc;
    g1_set_inf(&acc);
    for (uint64_t i = 0; i < len; i++) {
        for (int half = 1; half >= 0; half--) {
            unsigned nib = half ? (be[i] >> 4) : (be[i] & 0xF);
            for (int k = 0; k < 4; k++) g1_dbl(&acc, &acc);
            if (nib) g1_add(&acc, &acc, &table[nib]);
        }
    }
    *r = acc;
}

static void g2_mul_be(g2p *r, const g2p *p, const uint8_t *be, uint64_t len) {
    g2p table[16];
    g2_set_inf(&table[0]);
    table[1] = *p;
    for (int i = 2; i < 16; i++) g2_add(&table[i], &table[i - 1], p);
    g2p acc;
    g2_set_inf(&acc);
    for (uint64_t i = 0; i < len; i++) {
        for (int half = 1; half >= 0; half--) {
            unsigned nib = half ? (be[i] >> 4) : (be[i] & 0xF);
            for (int k = 0; k < 4; k++) g2_dbl(&acc, &acc);
            if (nib) g2_add(&acc, &acc, &table[nib]);
        }
    }
    *r = acc;
}

/* ------------------------------------------------------------ pairing --- */

/* The Miller loop runs with the G2 point kept in affine coordinates on the
 * twisted curve E'(Fp2).  For the untwist (x, y) -> (x w^-2, y w^-3) the
 * tangent/chord slope of the untwisted point is lambda' * w^-1 with
 * lambda' the slope on E', so the line through the untwisted T evaluated
 * at an embedded G1 point (px, py) is (using w^-k = w^(6-k) * xi^-1):
 *
 *     l = py + (lambda'*tx - ty) xi^-1 w^3 - lambda' px xi^-1 w^5
 *
 * — a sparse Fp12 element with coefficients only at w^0 (Fp), w^3, w^5.
 * This is algebraically identical to the Python oracle's generic-Fp12
 * line (crypto/pairing.py), so the Miller value matches bit-for-bit. */

static fp2 XI_INV; /* (1+u)^-1 — set in init */
static fp2 FROB1_G[6]; /* gamma1_i = xi^(i(p-1)/6) — set in init */
static fp2 PSI_X, PSI_Y; /* untwist-frobenius-twist constants — set in init */

/* f *= l where l = py + a3 w^3 + a5 w^5 (py in Fp; a3, a5 in Fp2).
 * Coefficient slots: w^0 -> c0.c0, w^3 -> c1.c1, w^5 -> c1.c2, so
 * l.c0 = (py, 0, 0) and l.c1 = (0, a3, a5). */
static void fp12_mul_line(fp12 *f, const fp *py, const fp2 *a3, const fp2 *a5) {
    fp6 l1_f0, l1_f1, t;
    /* l.c1 * f->c0 and l.c1 * f->c1 with l.c1 = (0, a3, a5):
     * (a0,a1,a2)*(0,b1,b2) = (xi(a1 b2 + a2 b1), a0 b1 + xi a2 b2, a0 b2 + a1 b1) */
    fp2 u, v;
#define SPARSE6(dst, src) \
    do { \
        fp2_mul(&u, &(src)->c1, a5); \
        fp2_mul(&v, &(src)->c2, a3); \
        fp2_add(&u, &u, &v); \
        fp2_mul_xi(&(dst).c0, &u); \
        fp2_mul(&u, &(src)->c0, a3); \
        fp2_mul(&v, &(src)->c2, a5); \
        fp2_mul_xi(&v, &v); \
        fp2_add(&(dst).c1, &u, &v); \
        fp2_mul(&u, &(src)->c0, a5); \
        fp2_mul(&v, &(src)->c1, a3); \
        fp2_add(&(dst).c2, &u, &v); \
    } while (0)
    SPARSE6(l1_f0, &f->c0);
    SPARSE6(l1_f1, &f->c1);
#undef SPARSE6
    /* r.c0 = py*f.c0 + v*(f.c1 * l.c1);  r.c1 = py*f.c1 + f.c0 * l.c1 */
    fp6 c0, c1;
    fp2_mul_fp(&c0.c0, &f->c0.c0, py);
    fp2_mul_fp(&c0.c1, &f->c0.c1, py);
    fp2_mul_fp(&c0.c2, &f->c0.c2, py);
    fp6_mul_v(&t, &l1_f1);
    fp6_add(&c0, &c0, &t);
    fp2_mul_fp(&c1.c0, &f->c1.c0, py);
    fp2_mul_fp(&c1.c1, &f->c1.c1, py);
    fp2_mul_fp(&c1.c2, &f->c1.c2, py);
    fp6_add(&c1, &c1, &l1_f0);
    f->c0 = c0;
    f->c1 = c1;
}

/* f *= l for a vertical line l = px - tx w^4 xi^-1 (w^4 -> c0.c2 slot). */
static void fp12_mul_vline(fp12 *f, const fp *px, const fp2 *a4) {
    /* l.c0 = (px, 0, a4), l.c1 = 0:
     * (a0,a1,a2)*(b0,0,b2) = (a0 b0 + xi(a1 b2), a1 b0 + xi a2 b2, a2 b0 + a0 b2) */
    fp6 c0, c1;
    fp2 u, v;
#define VSPARSE6(dst, src) \
    do { \
        fp2_mul_fp(&u, &(src)->c0, px); \
        fp2_mul(&v, &(src)->c1, a4); \
        fp2_mul_xi(&v, &v); \
        fp2_add(&(dst).c0, &u, &v); \
        fp2_mul_fp(&u, &(src)->c1, px); \
        fp2_mul(&v, &(src)->c2, a4); \
        fp2_mul_xi(&v, &v); \
        fp2_add(&(dst).c1, &u, &v); \
        fp2_mul_fp(&u, &(src)->c2, px); \
        fp2_mul(&v, &(src)->c0, a4); \
        fp2_add(&(dst).c2, &u, &v); \
    } while (0)
    VSPARSE6(c0, &f->c0);
    VSPARSE6(c1, &f->c1);
#undef VSPARSE6
    f->c0 = c0;
    f->c1 = c1;
}

/* T on E'(Fp2), affine with infinity flag. */
typedef struct { fp2 x, y; int inf; } e2a;

/* shared tail of a Miller step once lambda' is known: multiply the line
 * into f and move T to (lam^2 - tx - ox, lam(tx - x3) - ty). */
static void miller_apply(fp12 *f, e2a *t, const fp2 *lam, const fp2 *other_x,
                         const fp *px, const fp *py) {
    fp2 a3, a5, tmp, x3, y3;
    /* a3 = (lam*tx - ty) * xi^-1;  a5 = -lam*px * xi^-1 */
    fp2_mul(&a3, lam, &t->x);
    fp2_sub(&a3, &a3, &t->y);
    fp2_mul(&a3, &a3, &XI_INV);
    fp2_mul_fp(&a5, lam, px);
    fp2_neg(&a5, &a5);
    fp2_mul(&a5, &a5, &XI_INV);
    fp12_mul_line(f, py, &a3, &a5);
    fp2_sqr(&x3, lam);
    fp2_sub(&x3, &x3, &t->x);
    fp2_sub(&x3, &x3, other_x);
    fp2_sub(&tmp, &t->x, &x3);
    fp2_mul(&y3, lam, &tmp);
    fp2_sub(&y3, &y3, &t->y);
    t->x = x3;
    t->y = y3;
}

static void tangent_lambda(fp2 *lam, const e2a *t) {
    fp2 num, den;
    fp2_sqr(&num, &t->x);
    fp2_add(&den, &num, &num);
    fp2_add(&num, &den, &num); /* 3 x^2 */
    fp2_add(&den, &t->y, &t->y);
    fp2_inv(&den, &den);
    fp2_mul(lam, &num, &den);
}

static void miller_step_dbl(fp12 *f, e2a *t, const fp *px, const fp *py) {
    fp12_sqr(f, f);
    if (t->inf) return;
    fp2 lam;
    tangent_lambda(&lam, t);
    fp2 tx = t->x;
    miller_apply(f, t, &lam, &tx, px, py);
}

static void miller_step_add(fp12 *f, e2a *t, const e2a *q,
                            const fp *px, const fp *py) {
    if (t->inf) { *t = *q; return; }
    if (q->inf) return;
    fp2 lam;
    if (fp2_eq(&t->x, &q->x)) {
        if (!fp2_eq(&t->y, &q->y)) {
            /* vertical: l = px - tx w^4 xi^-1, then t + q = O */
            fp2 a4;
            fp2_mul(&a4, &t->x, &XI_INV);
            fp2_neg(&a4, &a4);
            fp12_mul_vline(f, px, &a4);
            t->inf = 1;
            return;
        }
        tangent_lambda(&lam, t);
    } else {
        fp2 dy, dx;
        fp2_sub(&dy, &q->y, &t->y);
        fp2_sub(&dx, &q->x, &t->x);
        fp2_inv(&dx, &dx);
        fp2_mul(&lam, &dy, &dx);
    }
    miller_apply(f, t, &lam, &q->x, px, py);
}

/* Miller loop f_{|x|,Q}(P), conjugated for x < 0.  P affine in G1,
 * Q affine in G2 (coords in Fp2 on the twist).  Step ordering mirrors
 * crypto/pairing.py (tangent at pre-doubling t; addition chord through
 * (t_new, q)), so the Fp12 value matches the Python oracle exactly. */
static void miller_loop(fp12 *f, const fp *p1x, const fp *p1y, int p1_inf,
                        const fp2 *q2x, const fp2 *q2y, int q2_inf) {
    fp12_one(f);
    if (p1_inf || q2_inf) return;
    e2a q = { *q2x, *q2y, 0 }, t = q;
    for (int bit = 62; bit >= 0; bit--) {
        miller_step_dbl(f, &t, p1x, p1y);
        if ((BLS_X_ABS >> bit) & 1) miller_step_add(f, &t, &q, p1x, p1y);
    }
    fp12 c;
    fp12_conj(&c, f);
    *f = c;
}

static void fp12_frob1(fp12 *r, const fp12 *a) {
    fp2 *rc[6] = { &r->c0.c0, &r->c1.c0, &r->c0.c1, &r->c1.c1, &r->c0.c2, &r->c1.c2 };
    const fp2 *ac[6] = { &a->c0.c0, &a->c1.c0, &a->c0.c1, &a->c1.c1, &a->c0.c2, &a->c1.c2 };
    for (int i = 0; i < 6; i++) {
        fp2 c;
        fp2_conj(&c, ac[i]);
        fp2_mul(rc[i], &c, &FROB1_G[i]);
    }
}

/* f^x for the (negative) BLS parameter; valid in the cyclotomic subgroup
 * where inversion is conjugation. */
static void fp12_powx(fp12 *r, const fp12 *f) {
    fp12 acc = *f;
    for (int bit = 62; bit >= 0; bit--) {
        fp12_sqr(&acc, &acc);
        if ((BLS_X_ABS >> bit) & 1) fp12_mul(&acc, &acc, f);
    }
    fp12_conj(r, &acc);
}

/* shared easy part: f^((p^6-1)(p^2+1)) */
static void final_exp_easy(fp12 *r, const fp12 *f) {
    fp12 c, i, t, u;
    fp12_conj(&c, f);
    fp12_inv(&i, f);
    fp12_mul(&t, &c, &i);
    fp12_frob2(&u, &t);
    fp12_mul(r, &u, &t);
}

/* exact final exponentiation (naive hard part) — used where the GT value
 * itself is exported and must equal the Python oracle. */
static void final_exponentiation(fp12 *r, const fp12 *f) {
    fp12 t;
    final_exp_easy(&t, f);
    fp12_pow_limbs(r, &t, HARD_EXP, HARD_EXP_LIMBS, HARD_EXP_BITS);
}

/* fast membership check: computes m^(3*hard) via
 * 3H = (x-1)^2 (x+p)(x^2+p^2-1) + 3 (verified in gen_bls_consts.py);
 * since gcd(3, r) = 1 this is 1 iff m^H is 1. */
static int final_exp_is_one_fast(const fp12 *f) {
    fp12 m, a, b, c, d, e, g, t;
    final_exp_easy(&m, f);
    fp12_powx(&a, &m);
    fp12_conj(&t, &m);
    fp12_mul(&a, &a, &t); /* m^(x-1) */
    fp12_powx(&b, &a);
    fp12_conj(&t, &a);
    fp12_mul(&b, &b, &t); /* m^((x-1)^2) */
    fp12_powx(&c, &b);
    fp12_frob1(&t, &b);
    fp12_mul(&c, &c, &t); /* b^(x+p) */
    fp12_powx(&d, &c);
    fp12_powx(&d, &d); /* c^(x^2) */
    fp12_frob2(&e, &c); /* c^(p^2) */
    fp12_mul(&g, &d, &e);
    fp12_conj(&t, &c);
    fp12_mul(&g, &g, &t); /* c^(x^2+p^2-1) */
    /* times m^3 */
    fp12_sqr(&t, &m);
    fp12_mul(&t, &t, &m);
    fp12_mul(&g, &g, &t);
    return fp12_is_one(&g);
}

/* --------------------------------------------------------------- init --- */

static int g_initialized = 0;
static fp C390; /* raw residue 2^390 mod p — set in init */

/* Runs at dlopen time (single-threaded, before ctypes returns the handle),
 * so no caller can ever observe partially-built Frobenius/psi tables even
 * though ctypes releases the GIL around calls. ensure_init() stays as a
 * belt-and-braces guard for non-dlopen embeddings. */
__attribute__((constructor)) static void bls_init_ctor(void);

static void ensure_init(void) {
    if (g_initialized) return;
    /* gamma powers for frobenius^2 */
    fp gamma;
    fp_from_plain(&gamma, FROB2_GAMMA);
    fp_one(&FROB2_POWS[0]);
    for (int i = 1; i < 6; i++) fp_mul(&FROB2_POWS[i], &FROB2_POWS[i - 1], &gamma);
    fp_from_plain(&XI_INV.c0, XI_INV_C0);
    fp_from_plain(&XI_INV.c1, XI_INV_C1);
    const uint64_t *g1c[6][2] = {
        {FROB1_G0_C0, FROB1_G0_C1}, {FROB1_G1_C0, FROB1_G1_C1},
        {FROB1_G2_C0, FROB1_G2_C1}, {FROB1_G3_C0, FROB1_G3_C1},
        {FROB1_G4_C0, FROB1_G4_C1}, {FROB1_G5_C0, FROB1_G5_C1},
    };
    for (int i = 0; i < 6; i++) {
        fp_from_plain(&FROB1_G[i].c0, g1c[i][0]);
        fp_from_plain(&FROB1_G[i].c1, g1c[i][1]);
    }
    fp_from_plain(&PSI_X.c0, PSI_X_C0);
    fp_from_plain(&PSI_X.c1, PSI_X_C1);
    fp_from_plain(&PSI_Y.c0, PSI_Y_C0);
    fp_from_plain(&PSI_Y.c1, PSI_Y_C1);
    {
        /* C390 holds the RAW value 2^390 mod p: fp_from_plain(64) computes
         * 64*2^384 mod p and stores it without a final from-Montgomery
         * step, which is exactly the plain residue 2^390 mod p.  Used to
         * emit values in the device kernel's 2^390-Montgomery encoding
         * (ops/lazy_limbs.py R = 2^390) with a single fp_mul. */
        uint64_t sixty_four[6] = {64, 0, 0, 0, 0, 0};
        fp_from_plain(&C390, sixty_four);
    }
    g_initialized = 1;
}

__attribute__((constructor)) static void bls_init_ctor(void) { ensure_init(); }

/* ------------------------------------------------------- byte helpers --- */

static void scalar_from_be32(uint64_t out[4], const uint8_t in[32]) {
    for (int i = 0; i < 4; i++) {
        uint64_t v = 0;
        const uint8_t *p = in + (3 - i) * 8;
        for (int j = 0; j < 8; j++) v = (v << 8) | p[j];
        out[i] = v;
    }
}

static void g1_load(fp *x, fp *y, const uint8_t in[96]) {
    fp_from_be(x, in);
    fp_from_be(y, in + 48);
}

static void g1_store(uint8_t out[96], const fp *x, const fp *y) {
    fp_to_be(out, x);
    fp_to_be(out + 48, y);
}

static void g2_load(fp2 *x, fp2 *y, const uint8_t in[192]) {
    fp_from_be(&x->c0, in);
    fp_from_be(&x->c1, in + 48);
    fp_from_be(&y->c0, in + 96);
    fp_from_be(&y->c1, in + 144);
}

static void g2_store(uint8_t out[192], const fp2 *x, const fp2 *y) {
    fp_to_be(out, &x->c0);
    fp_to_be(out + 48, &x->c1);
    fp_to_be(out + 96, &y->c0);
    fp_to_be(out + 144, &y->c1);
}

/* ------------------------------------------------------------ exports --- */

void bls_g1_mul(const uint8_t in[96], uint8_t in_inf, const uint8_t scalar[32],
                uint8_t out[96], uint8_t *out_inf) {
    ensure_init();
    uint64_t sc[4];
    scalar_from_be32(sc, scalar);
    if (in_inf) { memset(out, 0, 96); *out_inf = 1; return; }
    fp x, y;
    g1_load(&x, &y, in);
    g1p p, r;
    g1_from_affine(&p, &x, &y);
    g1_mul_scalar(&r, &p, sc);
    int inf;
    g1_to_affine(&x, &y, &inf, &r);
    *out_inf = (uint8_t)inf;
    g1_store(out, &x, &y);
}

void bls_g2_mul(const uint8_t in[192], uint8_t in_inf, const uint8_t scalar[32],
                uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    uint64_t sc[4];
    scalar_from_be32(sc, scalar);
    if (in_inf) { memset(out, 0, 192); *out_inf = 1; return; }
    fp2 x, y;
    g2_load(&x, &y, in);
    g2p p, r;
    g2_from_affine(&p, &x, &y);
    g2_mul_scalar(&r, &p, sc);
    int inf;
    g2_to_affine(&x, &y, &inf, &r);
    *out_inf = (uint8_t)inf;
    g2_store(out, &x, &y);
}

void bls_g1_aggregate(uint64_t n, const uint8_t *pts, const uint8_t *infs,
                      uint8_t out[96], uint8_t *out_inf) {
    ensure_init();
    g1p acc;
    g1_set_inf(&acc);
    for (uint64_t i = 0; i < n; i++) {
        if (infs[i]) continue;
        fp x, y;
        g1_load(&x, &y, pts + 96 * i);
        g1p p;
        g1_from_affine(&p, &x, &y);
        g1_add(&acc, &acc, &p);
    }
    fp x, y;
    int inf;
    g1_to_affine(&x, &y, &inf, &acc);
    *out_inf = (uint8_t)inf;
    g1_store(out, &x, &y);
}

void bls_g2_aggregate(uint64_t n, const uint8_t *pts, const uint8_t *infs,
                      uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    g2p acc;
    g2_set_inf(&acc);
    for (uint64_t i = 0; i < n; i++) {
        if (infs[i]) continue;
        fp2 x, y;
        g2_load(&x, &y, pts + 192 * i);
        g2p p;
        g2_from_affine(&p, &x, &y);
        g2_add(&acc, &acc, &p);
    }
    fp2 x, y;
    int inf;
    g2_to_affine(&x, &y, &inf, &acc);
    *out_inf = (uint8_t)inf;
    g2_store(out, &x, &y);
}

static unsigned msm_window(uint64_t n) {
    if (n < 4) return 2;
    if (n < 16) return 4;
    if (n < 128) return 6;
    if (n < 1024) return 9;
    return 12;
}

void bls_g1_msm(uint64_t n, const uint8_t *pts, const uint8_t *infs,
                const uint8_t *scalars, uint8_t out[96], uint8_t *out_inf) {
    ensure_init();
    unsigned c = msm_window(n);
    unsigned nbuckets = (1u << c) - 1;
    g1p *points = malloc(n * sizeof(g1p));
    uint64_t (*scs)[4] = malloc(n * sizeof(*scs));
    g1p *buckets = malloc(nbuckets * sizeof(g1p));
    for (uint64_t i = 0; i < n; i++) {
        if (infs[i]) { g1_set_inf(&points[i]); memset(scs[i], 0, 32); continue; }
        fp x, y;
        g1_load(&x, &y, pts + 96 * i);
        g1_from_affine(&points[i], &x, &y);
        scalar_from_be32(scs[i], scalars + 32 * i);
    }
    g1p result;
    g1_set_inf(&result);
    int nwin = (256 + c - 1) / c;
    for (int win = nwin - 1; win >= 0; win--) {
        for (unsigned k = 0; k < c; k++) g1_dbl(&result, &result);
        for (unsigned b = 0; b < nbuckets; b++) g1_set_inf(&buckets[b]);
        unsigned lo = win * c;
        for (uint64_t i = 0; i < n; i++) {
            if (g1_is_inf(&points[i])) continue;
            unsigned idx = 0;
            for (unsigned b = 0; b < c; b++) {
                unsigned bit = lo + b;
                if (bit < 256 && ((scs[i][bit / 64] >> (bit % 64)) & 1)) idx |= 1u << b;
            }
            if (idx) g1_add(&buckets[idx - 1], &buckets[idx - 1], &points[i]);
        }
        g1p running, acc;
        g1_set_inf(&running);
        g1_set_inf(&acc);
        for (int b = (int)nbuckets - 1; b >= 0; b--) {
            g1_add(&running, &running, &buckets[b]);
            g1_add(&acc, &acc, &running);
        }
        g1_add(&result, &result, &acc);
    }
    free(points);
    free(scs);
    free(buckets);
    fp x, y;
    int inf;
    g1_to_affine(&x, &y, &inf, &result);
    *out_inf = (uint8_t)inf;
    g1_store(out, &x, &y);
}

void bls_g2_msm(uint64_t n, const uint8_t *pts, const uint8_t *infs,
                const uint8_t *scalars, uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    unsigned c = msm_window(n);
    unsigned nbuckets = (1u << c) - 1;
    g2p *points = malloc(n * sizeof(g2p));
    uint64_t (*scs)[4] = malloc(n * sizeof(*scs));
    g2p *buckets = malloc(nbuckets * sizeof(g2p));
    for (uint64_t i = 0; i < n; i++) {
        if (infs[i]) { g2_set_inf(&points[i]); memset(scs[i], 0, 32); continue; }
        fp2 x, y;
        g2_load(&x, &y, pts + 192 * i);
        g2_from_affine(&points[i], &x, &y);
        scalar_from_be32(scs[i], scalars + 32 * i);
    }
    g2p result;
    g2_set_inf(&result);
    int nwin = (256 + c - 1) / c;
    for (int win = nwin - 1; win >= 0; win--) {
        for (unsigned k = 0; k < c; k++) g2_dbl(&result, &result);
        for (unsigned b = 0; b < nbuckets; b++) g2_set_inf(&buckets[b]);
        unsigned lo = win * c;
        for (uint64_t i = 0; i < n; i++) {
            if (g2_is_inf(&points[i])) continue;
            unsigned idx = 0;
            for (unsigned b = 0; b < c; b++) {
                unsigned bit = lo + b;
                if (bit < 256 && ((scs[i][bit / 64] >> (bit % 64)) & 1)) idx |= 1u << b;
            }
            if (idx) g2_add(&buckets[idx - 1], &buckets[idx - 1], &points[i]);
        }
        g2p running, acc;
        g2_set_inf(&running);
        g2_set_inf(&acc);
        for (int b = (int)nbuckets - 1; b >= 0; b--) {
            g2_add(&running, &running, &buckets[b]);
            g2_add(&acc, &acc, &running);
        }
        g2_add(&result, &result, &acc);
    }
    free(points);
    free(scs);
    free(buckets);
    fp2 x, y;
    int inf;
    g2_to_affine(&x, &y, &inf, &result);
    *out_inf = (uint8_t)inf;
    g2_store(out, &x, &y);
}

int bls_g1_in_subgroup(const uint8_t in[96]) {
    ensure_init();
    fp x, y;
    g1_load(&x, &y, in);
    g1p p, r;
    g1_from_affine(&p, &x, &y);
    uint64_t order[4];
    memcpy(order, CURVE_ORDER_R, sizeof order);
    g1_mul_scalar(&r, &p, order);
    return g1_is_inf(&r);
}

/* p and (p - 1) / 2 as 48 big-endian bytes: what a compressed x and the
 * sign of y are compared with. */
static void g1_compress_bounds(uint8_t pbe[48], uint8_t halfbe[48]) {
    uint64_t half[6];
    for (int i = 0; i < 6; i++) {
        half[i] = FP_P[i] >> 1;
        if (i < 5) half[i] |= FP_P[i + 1] << 63;
    }
    for (int i = 0; i < 6; i++)
        for (int j = 0; j < 8; j++) {
            pbe[48 - 1 - (8 * i + j)] = (uint8_t)(FP_P[i] >> (8 * j));
            halfbe[48 - 1 - (8 * i + j)] = (uint8_t)(half[i] >> (8 * j));
        }
}

/* One compressed point that is not the point at infinity, as
 * crypto/curve.g1_from_bytes decides it (compressed flag, x < p, on the
 * curve, the sign flag, in the subgroup): out takes its 96-byte affine
 * form. Returns 1 where it is such a point, 0 otherwise (the infinity
 * flag among the refusals). */
static int g1_decompress_one(const uint8_t key[48], uint8_t out[96],
                             const uint8_t pbe[48], const uint8_t halfbe[48]) {
    int flags = key[0];
    if (!(flags & 0x80) || (flags & 0x40)) return 0;
    uint8_t xb[48], yb[48];
    memcpy(xb, key, 48);
    xb[0] &= 0x1F;
    if (memcmp(xb, pbe, 48) >= 0) return 0;
    fp x, y, y2, four;
    fp_from_be(&x, xb);
    fp_sqr(&y2, &x);
    fp_mul(&y2, &y2, &x);
    fp_one(&four);
    fp_add(&four, &four, &four);
    fp_add(&four, &four, &four);
    fp_add(&y2, &y2, &four);
    if (!fp_sqrt(&y, &y2)) return 0;
    fp_to_be(yb, &y);
    if ((memcmp(yb, halfbe, 48) > 0) != ((flags & 0x20) ? 1 : 0)) fp_neg(&y, &y);
    g1_store(out, &x, &y);
    return bls_g1_in_subgroup(out);
}

/* KeyValidate of n compressed public keys (infinity refused): out takes
 * the 96-byte affine points. Returns the place of the first key that
 * fails, n when none does. One call a slice of a registry, so that
 * threads share the work. */
uint64_t bls_g1_key_validate_many(uint64_t n, const uint8_t *in, uint8_t *out) {
    ensure_init();
    uint8_t pbe[48], halfbe[48];
    g1_compress_bounds(pbe, halfbe);
    for (uint64_t k = 0; k < n; k++)
        if (!g1_decompress_one(in + 48 * k, out + 96 * k, pbe, halfbe)) return k;
    return n;
}

/* n compressed G1 points as crypto/kzg.validate_kzg_g1 admits them: a
 * point of the subgroup, or the ONE encoding of the point at infinity
 * (0xc0 and 47 zero bytes), which KeyValidate refuses and a KZG proof
 * or commitment may be. status[k] takes 1 for a point (its affine form
 * in out), 2 for infinity, 0 for bytes that are neither; every point is
 * decided, so one bad proof costs its own sidecar only. */
void bls_g1_decompress_many(uint64_t n, const uint8_t *in, uint8_t *out, uint8_t *status) {
    ensure_init();
    uint8_t pbe[48], halfbe[48], inf[48] = {0xC0};
    g1_compress_bounds(pbe, halfbe);
    for (uint64_t k = 0; k < n; k++) {
        if (memcmp(in + 48 * k, inf, 48) == 0) {
            memset(out + 96 * k, 0, 96);
            status[k] = 2;
        } else {
            status[k] = (uint8_t)g1_decompress_one(in + 48 * k, out + 96 * k, pbe, halfbe);
        }
    }
}

/* psi(x, y) = (conj(x) * PSI_X, conj(y) * PSI_Y) on E'(Fp2). */
static void g2_psi(fp2 *rx, fp2 *ry, const fp2 *x, const fp2 *y) {
    fp2 cx, cy;
    fp2_conj(&cx, x);
    fp2_conj(&cy, y);
    fp2_mul(rx, &cx, &PSI_X);
    fp2_mul(ry, &cy, &PSI_Y);
}

/* psi on Jacobian coordinates: X/Z^2, Y/Z^3 transform coordinate-wise
 * under conj (a field automorphism), so (conj(X)*PSI_X, conj(Y)*PSI_Y,
 * conj(Z)) represents psi of the affine point — no inversion needed. */
static void g2_psi_jac(g2p *r, const g2p *p) {
    if (g2_is_inf(p)) { g2_set_inf(r); return; }
    fp2 cx, cy, cz;
    fp2_conj(&cx, &p->X);
    fp2_conj(&cy, &p->Y);
    fp2_conj(&cz, &p->Z);
    fp2_mul(&r->X, &cx, &PSI_X);
    fp2_mul(&r->Y, &cy, &PSI_Y);
    r->Z = cz;
}

/* [|x|]P by plain double-and-add: the BLS parameter has Hamming weight 6
 * (bits 63,62,60,57,48,16), so 63 doublings + 5 additions with no window
 * table — ~40% fewer point ops than the generic nibble-window path. */
static void g2_mul_z(g2p *r, const g2p *p) {
    g2p acc = *p;
    for (int bit = 62; bit >= 0; bit--) {
        g2_dbl(&acc, &acc);
        if ((BLS_X_ABS >> bit) & 1) g2_add(&acc, &acc, p);
    }
    *r = acc;
}

/* Bowe's criterion: Q in G2 iff psi(Q) == [x]Q (x the negative BLS
 * parameter), i.e. psi(Q) == -[|x|]Q.  ~4x cheaper than mul-by-r. */
int bls_g2_in_subgroup(const uint8_t in[192]) {
    ensure_init();
    fp2 x, y, px, py;
    g2_load(&x, &y, in);
    g2_psi(&px, &py, &x, &y);
    g2p p, r;
    g2_from_affine(&p, &x, &y);
    g2_mul_z(&r, &p);
    fp2 rx, ry;
    int inf;
    g2_to_affine(&rx, &ry, &inf, &r);
    if (inf) return 0; /* [|x|]Q = O can't equal psi(Q) of a finite Q */
    fp2_neg(&ry, &ry); /* -[|x|]Q */
    return fp2_eq(&rx, &px) && fp2_eq(&ry, &py);
}

/* Budroni-Pintore cofactor clearing, exactly equal to [h_eff]Q on E2:
 * [x^2-x-1]Q + [x-1]psi(Q) + psi^2([2]Q), x < 0, so with z = |x|:
 * [z^2+z-1]Q + [z+1](-psi(Q)) + psi^2([2]Q). */
void bls_g2_clear_cofactor(const uint8_t in[192], uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    fp2 x, y;
    g2_load(&x, &y, in);
    g2p q, acc;
    g2_from_affine(&q, &x, &y);
    /* Shared-ladder decomposition of the same group element:
     *   [z^2+z-1]Q = [z][z]Q + [z]Q - Q,  [z+1](-psi(Q)) = -psi([z+1]Q)
     * (psi is an endomorphism), so two plain [z]-ladders (HW(z)=6) plus
     * a handful of adds replace the previous 128-bit + 64-bit windowed
     * scalar muls — ~45% fewer point operations for the identical result. */
    g2p a, b, apq, t;
    g2_mul_z(&a, &q);  /* [z]Q */
    g2_mul_z(&b, &a);  /* [z^2]Q */
    g2_add(&apq, &a, &q); /* [z+1]Q */
    g2_psi_jac(&t, &apq); /* psi([z+1]Q) */
    /* acc = b + a - q - t */
    g2p nq = q, nt = t;
    fp2_neg(&nq.Y, &q.Y);
    fp2_neg(&nt.Y, &t.Y);
    g2_add(&acc, &b, &a);
    g2_add(&acc, &acc, &nq);
    g2_add(&acc, &acc, &nt);
    /* + psi^2([2]Q) */
    g2p dq, p2;
    g2_dbl(&dq, &q);
    g2_psi_jac(&p2, &dq);
    g2_psi_jac(&p2, &p2);
    g2_add(&acc, &acc, &p2);
    fp2 ox, oy;
    int inf;
    g2_to_affine(&ox, &oy, &inf, &acc);
    *out_inf = (uint8_t)inf;
    g2_store(out, &ox, &oy);
}

/* G2 decompression: x from the 96-byte IETF compressed form, y via
 * fp2_sqrt + the lexicographic-largest flag, then the psi-based subgroup
 * check. Returns 1 ok / 0 malformed; out is the 192-byte affine point,
 * out_inf set for the canonical infinity encoding. */
int bls_g2_decompress(const uint8_t in[96], uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    int flags = in[0];
    if (!(flags & 0x80)) return 0;
    if (flags & 0x40) {
        if (flags & 0x3F) return 0;
        for (int i = 1; i < 96; i++)
            if (in[i]) return 0;
        memset(out, 0, 192);
        *out_inf = 1;
        return 1;
    }
    uint8_t xb[96];
    memcpy(xb, in, 96);
    xb[0] &= 0x1F;
    /* canonical-range check BEFORE the Montgomery conversion */
    {
        /* compare both 48-byte limbs against p big-endian */
        uint8_t pbe[48];
        for (int i = 0; i < 6; i++)
            for (int j = 0; j < 8; j++)
                pbe[48 - 1 - (8 * i + j)] = (uint8_t)(FP_P[i] >> (8 * j));
        if (memcmp(xb, pbe, 48) >= 0) return 0;      /* x.c1 (imaginary first) */
        if (memcmp(in + 48, pbe, 48) >= 0) return 0; /* x.c0 */
    }
    fp2 x, y2, y;
    /* serialization order: c1 (imaginary) first, then c0 */
    fp_from_be(&x.c1, xb);
    fp_from_be(&x.c0, in + 48);
    /* y^2 = x^3 + B2 with B2 = 4 + 4u (Montgomery 4 built from one) */
    fp2 t, b2;
    fp2_sqr(&t, &x);
    fp2_mul(&y2, &t, &x);
    {
        fp four;
        fp_one(&four);
        fp_add(&four, &four, &four);
        fp_add(&four, &four, &four);
        b2.c0 = four;
        b2.c1 = four;
    }
    fp2_add(&y2, &y2, &b2);
    if (!fp2_sqrt(&y, &y2)) return 0;
    /* lexicographic-largest flag: compare c1 first (imaginary most
     * significant), then c0, against (p-1)/2 — in canonical form */
    {
        uint8_t yb[96];
        fp_to_be(yb, &y.c1);
        fp_to_be(yb + 48, &y.c0);
        /* (p-1)/2 = p >> 1 (p odd) */
        uint64_t half[6];
        for (int i = 0; i < 6; i++) {
            half[i] = FP_P[i] >> 1;
            if (i < 5) half[i] |= FP_P[i + 1] << 63;
        }
        uint8_t halfbe[48];
        for (int i = 0; i < 6; i++)
            for (int j = 0; j < 8; j++)
                halfbe[48 - 1 - (8 * i + j)] = (uint8_t)(half[i] >> (8 * j));
        int is_zero_c1 = 1;
        for (int i = 0; i < 48; i++)
            if (yb[i]) { is_zero_c1 = 0; break; }
        int largest;
        if (!is_zero_c1)
            largest = memcmp(yb, halfbe, 48) > 0;
        else
            largest = memcmp(yb + 48, halfbe, 48) > 0;
        int want = (flags & 0x20) ? 1 : 0;
        if (largest != want) fp2_neg(&y, &y);
    }
    /* subgroup membership (psi check) */
    {
        uint8_t tmp[192];
        g2_store(tmp, &x, &y);
        if (!bls_g2_in_subgroup(tmp)) return 0;
    }
    g2_store(out, &x, &y);
    *out_inf = 0;
    return 1;
}

/* n compressed G2 points (96 bytes each) as bls_g2_decompress decides
 * them: status[k] takes 1 for a point of the subgroup (its affine form in
 * out), 2 for the point at infinity in its one encoding, 0 for bytes that
 * are neither; every point is decided, so one bad signature costs its own
 * item only. One call a slice of a flush's signatures. */
void bls_g2_decompress_many(uint64_t n, const uint8_t *in, uint8_t *out, uint8_t *status) {
    ensure_init();
    for (uint64_t k = 0; k < n; k++) {
        uint8_t inf = 0;
        int ok = bls_g2_decompress(in + 96 * k, out + 192 * k, &inf);
        status[k] = (uint8_t)(ok ? (inf ? 2 : 1) : 0);
    }
}

/* --------------------- RFC 9380 G2 map stage (SSWU + 3-isogeny) ---------
 * The hash-to-field half (expand_message_xmd) stays in Python (hashlib's
 * C SHA-256 is already fast); this entry performs everything after it:
 * SSWU on E2' for both field elements, addition on E2', the 3-isogeny to
 * E2, and Budroni-Pintore cofactor clearing. Ciphersuite parameters are
 * marshaled once from the Python side, whose copies are structurally
 * validated at import (crypto/hash_to_curve.py _validate_ciphersuite);
 * cross-check tests keep the two paths bit-identical. */

static fp2 MAP_A, MAP_B, MAP_Z;
static fp2 MAP_K[15]; /* K1[0..3], K2[0..2], K3[0..3], K4[0..3] */
static int map_params_set = 0;

void bls_g2_map_set_params(const uint8_t *in /* 18 * 96 bytes */) {
    ensure_init();
    fp2 *dst3[3] = {&MAP_A, &MAP_B, &MAP_Z};
    const uint8_t *p = in;
    for (int i = 0; i < 3; i++, p += 96) {
        fp_from_be(&dst3[i]->c0, p);
        fp_from_be(&dst3[i]->c1, p + 48);
    }
    for (int i = 0; i < 15; i++, p += 96) {
        fp_from_be(&MAP_K[i].c0, p);
        fp_from_be(&MAP_K[i].c1, p + 48);
    }
    map_params_set = 1;
}

/* RFC 9380 section 4.1 sgn0 for m=2: parity of the first nonzero limb
 * (parity read from the canonical, non-Montgomery representation). */
static int fp2_sgn0(const fp2 *a) {
    uint8_t b0[48], b1[48];
    fp_to_be(b0, &a->c0);
    fp_to_be(b1, &a->c1);
    int zero0 = 1;
    for (int i = 0; i < 48; i++)
        if (b0[i]) { zero0 = 0; break; }
    int s0 = b0[47] & 1;
    int s1 = b1[47] & 1;
    return s0 | (zero0 & s1);
}

/* Simplified SWU on E2' (RFC 9380 section 6.6.2), affine output. */
static void g2_sswu(fp2 *xo, fp2 *yo, const fp2 *u) {
    fp2 one, u2, tv1, tv2, t, x1, gx1, y;
    fp2_one(&one);
    fp2_sqr(&u2, u);
    fp2_mul(&tv1, &MAP_Z, &u2);
    fp2_sqr(&t, &tv1);
    fp2_add(&tv2, &t, &tv1);
    if (fp2_is_zero(&tv2)) {
        fp2 za, zai;
        fp2_mul(&za, &MAP_Z, &MAP_A);
        fp2_inv(&zai, &za);
        fp2_mul(&x1, &MAP_B, &zai);
    } else {
        fp2 tv2i, s, nb, ai;
        fp2_inv(&tv2i, &tv2);
        fp2_add(&s, &one, &tv2i);
        fp2_neg(&nb, &MAP_B);
        fp2_inv(&ai, &MAP_A);
        fp2_mul(&t, &nb, &ai);
        fp2_mul(&x1, &t, &s);
    }
    fp2_sqr(&t, &x1);
    fp2_add(&t, &t, &MAP_A);
    fp2_mul(&gx1, &t, &x1);
    fp2_add(&gx1, &gx1, &MAP_B);
    if (fp2_sqrt(&y, &gx1)) {
        *xo = x1;
    } else {
        fp2 x2, gx2;
        fp2_mul(&x2, &tv1, &x1);
        fp2_sqr(&t, &x2);
        fp2_add(&t, &t, &MAP_A);
        fp2_mul(&gx2, &t, &x2);
        fp2_add(&gx2, &gx2, &MAP_B);
        fp2_sqrt(&y, &gx2); /* gx1 non-square implies gx2 square */
        *xo = x2;
    }
    if (fp2_sgn0(u) != fp2_sgn0(&y)) fp2_neg(&y, &y);
    *yo = y;
}

/* Generic affine addition on E2' (a = MAP_A). Returns 0 when the sum is
 * the point at infinity. */
static int eprime_add(fp2 *rx, fp2 *ry, const fp2 *ax, const fp2 *ay,
                      const fp2 *bx, const fp2 *by) {
    fp2 lam, num, den, t;
    if (fp2_eq(ax, bx)) {
        fp2 nby;
        fp2_neg(&nby, by);
        if (fp2_eq(ay, &nby)) return 0;
        /* doubling: lam = (3 x^2 + A) / (2 y) */
        fp2_sqr(&t, ax);
        fp2_add(&num, &t, &t);
        fp2_add(&num, &num, &t);
        fp2_add(&num, &num, &MAP_A);
        fp2_add(&den, ay, ay);
    } else {
        fp2_sub(&num, by, ay);
        fp2_sub(&den, bx, ax);
    }
    fp2 deni;
    fp2_inv(&deni, &den);
    fp2_mul(&lam, &num, &deni);
    fp2 x3, y3;
    fp2_sqr(&x3, &lam);
    fp2_sub(&x3, &x3, ax);
    fp2_sub(&x3, &x3, bx);
    fp2_sub(&t, ax, &x3);
    fp2_mul(&y3, &lam, &t);
    fp2_sub(&y3, &y3, ay);
    *rx = x3;
    *ry = y3;
    return 1;
}

static void fp2_horner(fp2 *r, const fp2 *k, int n, const fp2 *x) {
    *r = k[n - 1];
    for (int i = n - 2; i >= 0; i--) {
        fp2 t;
        fp2_mul(&t, r, x);
        fp2_add(r, &t, &k[i]);
    }
}

/* u_in: u0.c0 | u0.c1 | u1.c0 | u1.c1, 48-byte big-endian canonical each.
 * out: affine E2 point (192 bytes) after cofactor clearing. Returns -1 if
 * parameters were never set, 0 otherwise. */
int bls_g2_map_from_fields(const uint8_t u_in[192], uint8_t out[192],
                           uint8_t *out_inf) {
    ensure_init();
    if (!map_params_set) return -1;
    fp2 u0, u1, x0, y0, x1, y1, rx, ry;
    fp_from_be(&u0.c0, u_in);
    fp_from_be(&u0.c1, u_in + 48);
    fp_from_be(&u1.c0, u_in + 96);
    fp_from_be(&u1.c1, u_in + 144);
    g2_sswu(&x0, &y0, &u0);
    g2_sswu(&x1, &y1, &u1);
    if (!eprime_add(&rx, &ry, &x0, &y0, &x1, &y1)) {
        memset(out, 0, 192);
        *out_inf = 1;
        return 0;
    }
    /* 3-isogeny E2' -> E2 (a homomorphism, so adding before the map equals
     * the per-u mapping followed by addition on E2) */
    fp2 xn, xd, yn, yd;
    fp2_horner(&xn, &MAP_K[0], 4, &rx);
    fp2_horner(&xd, &MAP_K[4], 3, &rx);
    fp2_horner(&yn, &MAP_K[7], 4, &rx);
    fp2_horner(&yd, &MAP_K[11], 4, &rx);
    if (fp2_is_zero(&xd) || fp2_is_zero(&yd)) {
        /* isogeny pole = kernel point: maps to O */
        memset(out, 0, 192);
        *out_inf = 1;
        return 0;
    }
    fp2 xdi, ydi, ex, ey, t;
    fp2_inv(&xdi, &xd);
    fp2_mul(&ex, &xn, &xdi);
    fp2_inv(&ydi, &yd);
    fp2_mul(&t, &ry, &yn);
    fp2_mul(&ey, &t, &ydi);
    uint8_t tmp[192];
    g2_store(tmp, &ex, &ey);
    bls_g2_clear_cofactor(tmp, out, out_inf);
    return 0;
}

/* bls_g2_map_from_fields over n pairs of field elements laid end to end
 * (192 bytes each): out takes a point each, out_inf a flag each. Returns
 * -1 if the parameters were never set, 0 otherwise. One call a slice of
 * a flush's new messages. */
int bls_g2_map_from_fields_many(uint64_t n, const uint8_t *u_in, uint8_t *out,
                                uint8_t *out_inf) {
    ensure_init();
    if (!map_params_set) return -1;
    for (uint64_t k = 0; k < n; k++)
        bls_g2_map_from_fields(u_in + 192 * k, out + 192 * k, out_inf + k);
    return 0;
}

int bls_g1_on_curve(const uint8_t in[96]) {
    ensure_init();
    fp x, y, lhs, rhs, b;
    g1_load(&x, &y, in);
    fp_sqr(&lhs, &y);
    fp_sqr(&rhs, &x);
    fp_mul(&rhs, &rhs, &x);
    uint64_t four[6] = {4, 0, 0, 0, 0, 0};
    fp_from_plain(&b, four);
    fp_add(&rhs, &rhs, &b);
    return fp_eq(&lhs, &rhs);
}

int bls_g2_on_curve(const uint8_t in[192]) {
    ensure_init();
    fp2 x, y, lhs, rhs, b;
    g2_load(&x, &y, in);
    fp2_sqr(&lhs, &y);
    fp2_sqr(&rhs, &x);
    fp2_mul(&rhs, &rhs, &x);
    uint64_t four[6] = {4, 0, 0, 0, 0, 0};
    fp_from_plain(&b.c0, four);
    b.c1 = b.c0;
    fp2_add(&rhs, &rhs, &b);
    return fp2_eq(&lhs, &rhs);
}

/* inf_flags[i]: bit0 = G1 point i at infinity, bit1 = G2 point i. */
/* Multi-pairing: one SHARED Miller accumulator for all pairs, so the
 * fp12 squaring per loop iteration is paid once instead of once per pair
 * (the loop bits are identical for every pair; the accumulated product
 * equals the product of per-pair Miller values, and the x<0 conjugation
 * distributes over the product). The affine tangent denominators (2y,
 * never zero in odd-order G2) of all pairs are inverted together with the
 * Montgomery batch trick — 1 inversion + 3(m-1) muls per iteration
 * instead of m inversions. Addition steps keep per-pair inversion: the
 * BLS x parameter has Hamming weight 6, so they are rare. */
typedef struct { fp px, py; e2a q, t; } mpair;

/* In-place batch inversion of m nonzero values (Montgomery trick). */
static void fp2_batch_inv(fp2 *vals, fp2 *scratch, uint64_t m) {
    if (m == 0) return;
    scratch[0] = vals[0];
    for (uint64_t i = 1; i < m; i++) fp2_mul(&scratch[i], &scratch[i - 1], &vals[i]);
    fp2 inv;
    fp2_inv(&inv, &scratch[m - 1]);
    for (uint64_t i = m - 1; i > 0; i--) {
        fp2 t;
        fp2_mul(&t, &inv, &scratch[i - 1]); /* vals[i]^-1 */
        fp2_mul(&inv, &inv, &vals[i]);      /* running inv of prefix */
        vals[i] = t;
    }
    vals[0] = inv;
}

/* The shared-accumulator Miller walk over n pairs: f takes the product of
 * their Miller values, before the x<0 conjugation. Returns 1, or 0 where
 * a step degenerated or an allocation failed: its callers fail CLOSED. */
static int miller_multi(fp12 *f, uint64_t n, const uint8_t *g1s, const uint8_t *g2s,
                        const uint8_t *inf_flags) {
    mpair stack_pairs[16];
    fp2 stack_den[2 * 16];
    uint64_t stack_idx[16];
    mpair *pairs = n <= 16 ? stack_pairs : malloc(n * sizeof(mpair));
    fp2 *den = n <= 16 ? stack_den : malloc(2 * n * sizeof(fp2));
    uint64_t *idx = n <= 16 ? stack_idx : malloc(n * sizeof(uint64_t));
    if (pairs == NULL || den == NULL || idx == NULL) {
        /* fail CLOSED: a check that cannot run must never report valid */
        if (pairs != stack_pairs) free(pairs);
        if (den != stack_den) free(den);
        if (idx != stack_idx) free(idx);
        return 0;
    }
    fp2 *scratch = den + n;
    uint64_t live = 0;
    for (uint64_t i = 0; i < n; i++) {
        int g1_inf = inf_flags[i] & 1;
        int g2_inf = (inf_flags[i] >> 1) & 1;
        if (g1_inf || g2_inf) continue;
        mpair *m = &pairs[live++];
        g1_load(&m->px, &m->py, g1s + 96 * i);
        g2_load(&m->q.x, &m->q.y, g2s + 192 * i);
        m->q.inf = 0;
        m->t = m->q;
    }
    fp12_one(f);
    int degenerate = 0;
    for (int bit = 62; bit >= 0; bit--) {
        fp12_sqr(f, f);
        /* gather 2y denominators of the still-finite accumulators; a
         * y==0 accumulator (order-2 point, unreachable for subgroup
         * inputs) would poison the whole batch inversion — fail CLOSED */
        uint64_t m = 0;
        for (uint64_t i = 0; i < live; i++) {
            if (pairs[i].t.inf) continue;
            fp2_add(&den[m], &pairs[i].t.y, &pairs[i].t.y);
            if (fp2_is_zero(&den[m])) { degenerate = 1; break; }
            idx[m++] = i;
        }
        if (degenerate) break;
        fp2_batch_inv(den, scratch, m);
        for (uint64_t j = 0; j < m; j++) {
            mpair *p = &pairs[idx[j]];
            fp2 num, t3, lam, tx;
            fp2_sqr(&num, &p->t.x);
            fp2_add(&t3, &num, &num);
            fp2_add(&num, &t3, &num); /* 3 x^2 */
            fp2_mul(&lam, &num, &den[j]);
            tx = p->t.x;
            miller_apply(f, &p->t, &lam, &tx, &p->px, &p->py);
        }
        if ((BLS_X_ABS >> bit) & 1) {
            for (uint64_t i = 0; i < live; i++) {
                mpair *p = &pairs[i];
                miller_step_add(f, &p->t, &p->q, &p->px, &p->py);
            }
        }
    }
    if (pairs != stack_pairs) free(pairs);
    if (den != stack_den) free(den);
    if (idx != stack_idx) free(idx);
    return !degenerate;
}

int bls_pairing_check(uint64_t n, const uint8_t *g1s, const uint8_t *g2s,
                      const uint8_t *inf_flags) {
    ensure_init();
    fp12 f, c;
    if (!miller_multi(&f, n, g1s, g2s, inf_flags)) return 0;
    fp12_conj(&c, &f);
    return final_exp_is_one_fast(&c);
}

_Static_assert(sizeof(fp12) == 576, "an fp12 crosses the boundary as 576 bytes");

/* One slice of a pairing check: the Miller value of its n pairs into out,
 * the fp12 as the core holds it, for bls_pairing_final_check alone.
 * Returns 0 where bls_pairing_check would fail CLOSED. */
int bls_pairing_miller(uint64_t n, const uint8_t *g1s, const uint8_t *g2s,
                       const uint8_t *inf_flags, uint8_t *out) {
    ensure_init();
    fp12 f;
    int ok = miller_multi(&f, n, g1s, g2s, inf_flags);
    memcpy(out, &f, sizeof f);
    return ok;
}

/* The check over k slices' Miller values laid end to end: their product
 * is the Miller value of all their pairs (the walk's squarings and lines
 * multiply through), so this is bls_pairing_check's verdict over the
 * pairs of every slice, with ONE final exponentiation. */
int bls_pairing_final_check(uint64_t k, const uint8_t *fs) {
    ensure_init();
    fp12 f, t, c;
    fp12_one(&f);
    for (uint64_t i = 0; i < k; i++) {
        memcpy(&t, fs + sizeof t * i, sizeof t);
        fp12_mul(&f, &f, &t);
    }
    fp12_conj(&c, &f);
    return final_exp_is_one_fast(&c);
}

/* Emit a mont-form fp as the device pairing kernel's limb encoding:
 * 15 x 26-bit limbs (little-endian limb order, one u64 per limb) of the
 * plain residue v * 2^390 mod p (lazy_limbs R = 2^390).  One fp_mul by
 * the raw constant 2^390 mod p converts v*2^384 -> plain v*2^390. */
static void fp_to_dev_limbs(uint64_t out[15], const fp *a) {
    fp t;
    fp_mul(&t, a, &C390);
    for (int i = 0; i < 15; i++) {
        int bit = 26 * i, w = bit >> 6, off = bit & 63;
        uint64_t lo = t.l[w] >> off;
        if (off > 38 && w < 5) lo |= t.l[w + 1] << (64 - off);
        out[i] = lo & 0x3FFFFFFULL;
    }
}

static void fp2_to_dev_limbs(uint64_t out[30], const fp2 *a) {
    fp_to_dev_limbs(out, &a->c0);
    fp_to_dev_limbs(out + 15, &a->c1);
}

/* Lockstep affine ate walks for n subgroup G2 points, emitting the
 * per-step line coefficients the device Miller kernel consumes
 * (ops/pairing_device.prepare_g2 computes the same rows one point at a
 * time in Python; this is the batched native producer).  Output layout:
 * out[pair][step][coeff][fq2 c0|c1][15 limbs] with coeff 0 = a3 =
 * (lam*tx - ty)*xi^-1 and coeff 1 = lam*xi^-1, all in the device's
 * 2^390-Montgomery 26-bit limb encoding.  Tangent denominators are
 * inverted with one Montgomery batch inversion per step across all n
 * walks; the (rare) addition steps batch their chord denominators the
 * same way.  Returns the number of steps written per pair, or 0 on a
 * degenerate step (T at infinity / vertical chord — impossible for
 * subgroup inputs; callers fall back to the host oracle). */
uint64_t bls_g2_prepare_many(uint64_t n, const uint8_t *g2s, uint64_t *out) {
    ensure_init();
    if (n == 0) return 0;
    e2a *t = malloc(n * sizeof(e2a));
    e2a *q = malloc(n * sizeof(e2a));
    fp2 *den = malloc(2 * n * sizeof(fp2));
    if (t == NULL || q == NULL || den == NULL) {
        free(t); free(q); free(den);
        return 0;
    }
    fp2 *scratch = den + n;
    for (uint64_t i = 0; i < n; i++) {
        g2_load(&q[i].x, &q[i].y, g2s + 192 * i);
        q[i].inf = 0;
        t[i] = q[i];
    }
    const uint64_t stride = 2 * 2 * 15; /* u64s per (pair, step) */
    uint64_t total_steps = 0; /* 63 doublings + one add per set low bit */
    for (int bit = 62; bit >= 0; bit--)
        total_steps += 1 + ((BLS_X_ABS >> bit) & 1);
    uint64_t n_steps = 0;
    int ok = 1;
    uint64_t step = 0;
    for (int bit = 62; bit >= 0 && ok; bit--) {
        /* doubling: tangent at pre-doubling T.  A y==0 point (order 2)
         * would feed a zero denominator into the batch inversion and emit
         * garbage lines — honor the degenerate-step contract instead. */
        for (uint64_t i = 0; i < n; i++) {
            fp2_add(&den[i], &t[i].y, &t[i].y);
            if (fp2_is_zero(&den[i])) { ok = 0; break; }
        }
        if (!ok) break;
        fp2_batch_inv(den, scratch, n);
        for (uint64_t i = 0; i < n; i++) {
            fp2 num, t3, lam, a3, tmp, x3, y3;
            fp2_sqr(&num, &t[i].x);
            fp2_add(&t3, &num, &num);
            fp2_add(&num, &t3, &num); /* 3 tx^2 */
            fp2_mul(&lam, &num, &den[i]);
            fp2_mul(&a3, &lam, &t[i].x);
            fp2_sub(&a3, &a3, &t[i].y);
            fp2_mul(&a3, &a3, &XI_INV);
            fp2 lam_xi;
            fp2_mul(&lam_xi, &lam, &XI_INV);
            fp2_to_dev_limbs(out + (i * total_steps + step) * stride, &a3);
            fp2_to_dev_limbs(out + (i * total_steps + step) * stride + 30, &lam_xi);
            fp2_sqr(&x3, &lam);
            fp2_sub(&x3, &x3, &t[i].x);
            fp2_sub(&x3, &x3, &t[i].x);
            fp2_sub(&tmp, &t[i].x, &x3);
            fp2_mul(&y3, &lam, &tmp);
            fp2_sub(&y3, &y3, &t[i].y);
            t[i].x = x3;
            t[i].y = y3;
        }
        step++;
        if ((BLS_X_ABS >> bit) & 1) {
            /* addition: chord through post-doubling T and Q */
            for (uint64_t i = 0; i < n; i++) {
                if (fp2_eq(&t[i].x, &q[i].x)) { ok = 0; break; }
                fp2_sub(&den[i], &q[i].x, &t[i].x);
            }
            if (!ok) break;
            fp2_batch_inv(den, scratch, n);
            for (uint64_t i = 0; i < n; i++) {
                fp2 dy, lam, a3, lam_xi, tmp, x3, y3;
                fp2_sub(&dy, &q[i].y, &t[i].y);
                fp2_mul(&lam, &dy, &den[i]);
                fp2_mul(&a3, &lam, &t[i].x);
                fp2_sub(&a3, &a3, &t[i].y);
                fp2_mul(&a3, &a3, &XI_INV);
                fp2_mul(&lam_xi, &lam, &XI_INV);
                fp2_to_dev_limbs(out + (i * total_steps + step) * stride, &a3);
                fp2_to_dev_limbs(out + (i * total_steps + step) * stride + 30, &lam_xi);
                fp2_sqr(&x3, &lam);
                fp2_sub(&x3, &x3, &t[i].x);
                fp2_sub(&x3, &x3, &q[i].x);
                fp2_sub(&tmp, &t[i].x, &x3);
                fp2_mul(&y3, &lam, &tmp);
                fp2_sub(&y3, &y3, &t[i].y);
                t[i].x = x3;
                t[i].y = y3;
            }
            step++;
        }
    }
    n_steps = ok ? step : 0;
    free(t); free(q); free(den);
    return n_steps;
}

/* Single full pairing, result written as 12 * 48 bytes (flattened w^i
 * coefficient order: for i in 0..5 emit coeff_i.c0 then coeff_i.c1). */
void bls_pairing(const uint8_t g1[96], const uint8_t g2[192], uint8_t out[576]) {
    ensure_init();
    fp px, py;
    fp2 qx, qy;
    g1_load(&px, &py, g1);
    g2_load(&qx, &qy, g2);
    fp12 m, r;
    miller_loop(&m, &px, &py, 0, &qx, &qy, 0);
    final_exponentiation(&r, &m);
    const fp2 *coeffs[6] = { &r.c0.c0, &r.c1.c0, &r.c0.c1, &r.c1.c1, &r.c0.c2, &r.c1.c2 };
    for (int i = 0; i < 6; i++) {
        fp_to_be(out + 96 * i, &coeffs[i]->c0);
        fp_to_be(out + 96 * i + 48, &coeffs[i]->c1);
    }
}

void bls_g1_mul_wide(const uint8_t in[96], uint8_t in_inf, const uint8_t *scalar_be,
                     uint64_t sc_len, uint8_t out[96], uint8_t *out_inf) {
    ensure_init();
    if (in_inf) { memset(out, 0, 96); *out_inf = 1; return; }
    fp x, y;
    g1_load(&x, &y, in);
    g1p p, r;
    g1_from_affine(&p, &x, &y);
    g1_mul_be(&r, &p, scalar_be, sc_len);
    int inf;
    g1_to_affine(&x, &y, &inf, &r);
    *out_inf = (uint8_t)inf;
    g1_store(out, &x, &y);
}

void bls_g2_mul_wide(const uint8_t in[192], uint8_t in_inf, const uint8_t *scalar_be,
                     uint64_t sc_len, uint8_t out[192], uint8_t *out_inf) {
    ensure_init();
    if (in_inf) { memset(out, 0, 192); *out_inf = 1; return; }
    fp2 x, y;
    g2_load(&x, &y, in);
    g2p p, r;
    g2_from_affine(&p, &x, &y);
    g2_mul_be(&r, &p, scalar_be, sc_len);
    int inf;
    g2_to_affine(&x, &y, &inf, &r);
    *out_inf = (uint8_t)inf;
    g2_store(out, &x, &y);
}

int bls_fp_inv(const uint8_t in[48], uint8_t out[48]) {
    ensure_init();
    fp a, r;
    fp_from_be(&a, in);
    if (fp_is_zero(&a)) return 0;
    fp_inv(&r, &a);
    fp_to_be(out, &r);
    return 1;
}

int bls_fp2_inv(const uint8_t in[96], uint8_t out[96]) {
    ensure_init();
    fp2 a, r;
    fp_from_be(&a.c0, in);
    fp_from_be(&a.c1, in + 48);
    if (fp2_is_zero(&a)) return 0;
    fp2_inv(&r, &a);
    fp_to_be(out, &r.c0);
    fp_to_be(out + 48, &r.c1);
    return 1;
}

int bls_fp_sqrt(const uint8_t in[48], uint8_t out[48]) {
    ensure_init();
    fp a, r;
    fp_from_be(&a, in);
    if (!fp_sqrt(&r, &a)) return 0;
    fp_to_be(out, &r);
    return 1;
}

int bls_fp2_sqrt(const uint8_t in[96], uint8_t out[96]) {
    ensure_init();
    fp2 a, r;
    fp_from_be(&a.c0, in);
    fp_from_be(&a.c1, in + 48);
    if (!fp2_sqrt(&r, &a)) return 0;
    fp_to_be(out, &r.c0);
    fp_to_be(out + 48, &r.c1);
    return 1;
}

/* Montgomery round-trip and small algebraic identities; 0 = pass. */
int bls_selftest(void) {
    ensure_init();
    uint64_t plain[6] = {0x123456789abcdef0ULL, 0xfedcba9876543210ULL, 7, 0, 42, 0x10ULL};
    fp a, b, c, d;
    fp_from_plain(&a, plain);
    uint64_t back[6];
    fp_to_plain(back, &a);
    if (memcmp(back, plain, sizeof plain) != 0) return 1;
    /* (a+a)*a == a*a + a*a */
    fp_add(&b, &a, &a);
    fp_mul(&b, &b, &a);
    fp_sqr(&c, &a);
    fp_add(&c, &c, &c);
    if (!fp_eq(&b, &c)) return 2;
    /* a * a^-1 == 1 */
    fp_inv(&d, &a);
    fp_mul(&d, &d, &a);
    fp one;
    fp_one(&one);
    if (!fp_eq(&d, &one)) return 3;
    /* fp2 inversion */
    fp2 e = { a, c }, f, g;
    fp2_inv(&f, &e);
    fp2_mul(&g, &f, &e);
    fp2 o2;
    fp2_one(&o2);
    if (!fp2_eq(&g, &o2)) return 4;
    /* fp12 inversion */
    fp12 h, hi, hh, o12;
    fp6_zero(&h.c0);
    fp6_zero(&h.c1);
    h.c0.c0 = e;
    h.c1.c1 = e;
    h.c0.c2.c0 = a;
    fp12_inv(&hi, &h);
    fp12_mul(&hh, &hi, &h);
    fp12_one(&o12);
    if (!fp12_eq(&hh, &o12)) return 5;
    return 0;
}
