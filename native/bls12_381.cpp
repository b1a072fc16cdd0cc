// BLS12-381 host pairing backend (the blst-equivalent of SURVEY.md §2.6).
//
// Design notes (tpu-native repo, C++ host runtime side):
// - Fp: 6x64-bit little-endian limbs, Montgomery form, CIOS multiplication.
// - Fp12 as the sextic extension Fp2[w]/(w^6 - xi), xi = 1 + i.  Lines from
//   the M-twist land in the sparse slots {w^0, w^3, w^5} (untwist algebra:
//   x = x' w^4/xi, y = y' w^3/xi), so no 6/12 tower is needed.
// - Multi-pairing: affine Miller loop with per-step Montgomery batch
//   inversion across pairs; one shared final exponentiation using the
//   verified identity 3*(p^4-p^2+1)/r = (u-1)^2 (u+p)(u^2+p^2-1) + 3
//   (gcd(3, r) = 1, so the cubed check is equivalent for product==1).
// - All derived constants (Montgomery R^2, n0, frobenius gammas, iso
//   coefficients) are computed at init from p and the curve equation.
//
// Reference behavior parity: crypto/bls/src/impls/blst.rs (sign :187-220,
// verify_signature_sets :37-119), zcash compression flags.
#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <vector>
#include <thread>

typedef unsigned __int128 u128;
typedef uint64_t u64;
typedef uint8_t u8;

// ---------------------------------------------------------------------------
// Fp
// ---------------------------------------------------------------------------
struct Fp { u64 l[6]; };

static const u64 P_LIMBS[6] = {
    0xb9feffffffffaaabULL, 0x1eabfffeb153ffffULL, 0x6730d2a0f6b0f624ULL,
    0x64774b84f38512bfULL, 0x4b1ba7b6434bacd7ULL, 0x1a0111ea397fe69aULL};
static u64 N0;          // -p^{-1} mod 2^64
static Fp R2;           // (2^384)^2 mod p
static Fp TWO256_M;     // 2^256 in Montgomery form (hash_to_field split)
static Fp FP_ONE_M;     // 1 in Montgomery form
static Fp FP_ZERO = {{0,0,0,0,0,0}};

static inline int fp_cmp(const Fp&a, const Fp&b){
    for(int i=5;i>=0;i--){ if(a.l[i]<b.l[i])return -1; if(a.l[i]>b.l[i])return 1; }
    return 0;
}
static inline bool fp_is_zero(const Fp&a){
    u64 t=0; for(int i=0;i<6;i++) t|=a.l[i]; return t==0;
}
static inline void fp_sub_p(Fp&a){            // a -= p if a >= p
    Fp t; u128 br=0;
    for(int i=0;i<6;i++){ u128 d=(u128)a.l[i]-P_LIMBS[i]-br; t.l[i]=(u64)d; br=(d>>64)&1; }
    if(!br) a=t;
}
static inline void fp_add(Fp&o,const Fp&a,const Fp&b){
    u128 c=0;
    for(int i=0;i<6;i++){ c+=(u128)a.l[i]+b.l[i]; o.l[i]=(u64)c; c>>=64; }
    fp_sub_p(o);
}
static inline void fp_sub(Fp&o,const Fp&a,const Fp&b){
    u128 br=0; Fp t;
    for(int i=0;i<6;i++){ u128 d=(u128)a.l[i]-b.l[i]-br; t.l[i]=(u64)d; br=(d>>64)&1; }
    if(br){ u128 c=0; for(int i=0;i<6;i++){ c+=(u128)t.l[i]+P_LIMBS[i]; t.l[i]=(u64)c; c>>=64; } }
    o=t;
}
static inline void fp_neg(Fp&o,const Fp&a){
    if(fp_is_zero(a)){ o=a; return; }
    u128 br=0;
    for(int i=0;i<6;i++){ u128 d=(u128)P_LIMBS[i]-a.l[i]-br; o.l[i]=(u64)d; br=(d>>64)&1; }
}
// CIOS Montgomery multiplication
static void fp_mul(Fp&out,const Fp&a,const Fp&b){
    u64 t[8]={0,0,0,0,0,0,0,0};
    for(int i=0;i<6;i++){
        u128 c=0;
        for(int j=0;j<6;j++){ c+=(u128)t[j]+(u128)a.l[i]*b.l[j]; t[j]=(u64)c; c>>=64; }
        c+=t[6]; t[6]=(u64)c; t[7]=(u64)(c>>64);
        u64 m=t[0]*N0; c=(u128)t[0]+(u128)m*P_LIMBS[0]; c>>=64;
        for(int j=1;j<6;j++){ c+=(u128)t[j]+(u128)m*P_LIMBS[j]; t[j-1]=(u64)c; c>>=64; }
        c+=t[6]; t[5]=(u64)c; t[6]=t[7]+(u64)(c>>64);
    }
    for(int i=0;i<6;i++) out.l[i]=t[i];
    if(t[6]) { // subtract p once (t[6] can only be 0 or 1 here)
        u128 br=0;
        for(int i=0;i<6;i++){ u128 d=(u128)out.l[i]-P_LIMBS[i]-br; out.l[i]=(u64)d; br=(d>>64)&1; }
    } else fp_sub_p(out);
}
static inline void fp_sqr(Fp&o,const Fp&a){ fp_mul(o,a,a); }
static void fp_pow(Fp&o,const Fp&a,const u64*e,int elimbs){
    Fp r=FP_ONE_M, base=a; int top=elimbs*64-1;
    while(top>=0 && !((e[top/64]>>(top%64))&1)) top--;
    for(int i=top;i>=0;i--){
        fp_sqr(r,r);
        if((e[i/64]>>(i%64))&1) fp_mul(r,r,base);
        if(i==top){ r=base; }   // first set bit: start from base
    }
    o = (top<0)?FP_ONE_M:r;
}
static u64 PM2[6], PP1D4[6], PM3D4[6], PM1D2[6], PM1D6[6];  // exponents
static void fp_inv(Fp&o,const Fp&a){ fp_pow(o,a,PM2,6); }
static bool fp_sqrt(Fp&o,const Fp&a){
    Fp r; fp_pow(r,a,PP1D4,6);
    Fp chk; fp_sqr(chk,r);
    if(fp_cmp(chk,a)!=0) return false;
    o=r; return true;
}
static void fp_to_mont(Fp&o,const Fp&a){ fp_mul(o,a,R2); }
static void fp_from_mont(Fp&o,const Fp&a){ Fp one={{1,0,0,0,0,0}}; fp_mul(o,a,one); }
static void fp_from_be(Fp&o,const u8*b){   // 48 bytes big-endian -> plain limbs
    for(int i=0;i<6;i++){
        u64 v=0; for(int j=0;j<8;j++) v=(v<<8)|b[(5-i)*8+j];
        o.l[i]=v;
    }
}
static void fp_to_be(u8*b,const Fp&a){
    for(int i=0;i<6;i++) for(int j=0;j<8;j++) b[(5-i)*8+j]=(u8)(a.l[i]>>(56-8*j));
}
static bool fp_is_odd_plain(const Fp&m){ Fp p; fp_from_mont(p,m); return p.l[0]&1; }
static bool fp_lex_larger(const Fp&m){   // plain(a)*2 > p ?
    Fp p; fp_from_mont(p,m);
    Fp dbl; u128 c=0; u64 hi=0;
    for(int i=0;i<6;i++){ c+=((u128)p.l[i])<<1; dbl.l[i]=(u64)c; c>>=64; }
    hi=(u64)c;
    if(hi) return true;
    return fp_cmp(dbl,*(const Fp*)P_LIMBS)>0;
}

// ---------------------------------------------------------------------------
// Fp2 = Fp[i]/(i^2+1)
// ---------------------------------------------------------------------------
struct Fp2 { Fp c0, c1; };
static Fp2 FP2_ZERO, FP2_ONE;

static inline bool fp2_is_zero(const Fp2&a){ return fp_is_zero(a.c0)&&fp_is_zero(a.c1); }
static inline bool fp2_eq(const Fp2&a,const Fp2&b){ return fp_cmp(a.c0,b.c0)==0&&fp_cmp(a.c1,b.c1)==0; }
static inline void fp2_add(Fp2&o,const Fp2&a,const Fp2&b){ fp_add(o.c0,a.c0,b.c0); fp_add(o.c1,a.c1,b.c1); }
static inline void fp2_sub(Fp2&o,const Fp2&a,const Fp2&b){ fp_sub(o.c0,a.c0,b.c0); fp_sub(o.c1,a.c1,b.c1); }
static inline void fp2_neg(Fp2&o,const Fp2&a){ fp_neg(o.c0,a.c0); fp_neg(o.c1,a.c1); }
static inline void fp2_conj(Fp2&o,const Fp2&a){ o.c0=a.c0; fp_neg(o.c1,a.c1); }
static void fp2_mul(Fp2&o,const Fp2&a,const Fp2&b){
    Fp t0,t1,t2,t3,s0,s1;                       // Karatsuba
    fp_mul(t0,a.c0,b.c0); fp_mul(t1,a.c1,b.c1);
    fp_add(t2,a.c0,a.c1); fp_add(t3,b.c0,b.c1);
    fp_sub(s0,t0,t1);                           // c0 = a0b0 - a1b1
    fp_mul(s1,t2,t3); fp_sub(s1,s1,t0); fp_sub(s1,s1,t1); // c1 = (a0+a1)(b0+b1)-a0b0-a1b1
    o.c0=s0; o.c1=s1;
}
static void fp2_sqr(Fp2&o,const Fp2&a){
    Fp s,d,m;                                    // (a0+a1)(a0-a1), 2a0a1
    fp_add(s,a.c0,a.c1); fp_sub(d,a.c0,a.c1); fp_mul(m,a.c0,a.c1);
    fp_mul(o.c0,s,d); fp_add(o.c1,m,m);
}
static void fp2_mul_fp(Fp2&o,const Fp2&a,const Fp&s){ fp_mul(o.c0,a.c0,s); fp_mul(o.c1,a.c1,s); }
static void fp2_mul_xi(Fp2&o,const Fp2&a){       // * (1+i)
    Fp t0,t1; fp_sub(t0,a.c0,a.c1); fp_add(t1,a.c0,a.c1); o.c0=t0; o.c1=t1;
}
static void fp2_inv(Fp2&o,const Fp2&a){
    Fp n,t0,t1,ninv;
    fp_sqr(t0,a.c0); fp_sqr(t1,a.c1); fp_add(n,t0,t1);   // norm
    fp_inv(ninv,n);
    fp_mul(o.c0,a.c0,ninv);
    Fp negc1; fp_neg(negc1,a.c1); fp_mul(o.c1,negc1,ninv);
}
static void fp2_pow(Fp2&o,const Fp2&a,const u64*e,int elimbs){
    int top=elimbs*64-1;
    while(top>=0 && !((e[top/64]>>(top%64))&1)) top--;
    if(top<0){ o=FP2_ONE; return; }
    Fp2 r=a;
    for(int i=top-1;i>=0;i--){
        fp2_sqr(r,r);
        if((e[i/64]>>(i%64))&1) fp2_mul(r,r,a);
    }
    o=r;
}
static bool fp2_is_square(const Fp2&a){
    Fp n,t0,t1,leg;
    fp_sqr(t0,a.c0); fp_sqr(t1,a.c1); fp_add(n,t0,t1);
    if(fp_is_zero(n)) return true;
    fp_pow(leg,n,PM1D2,6);
    return fp_cmp(leg,FP_ONE_M)==0;
}
static bool fp2_sqrt(Fp2&o,const Fp2&a){
    if(fp2_is_zero(a)){ o=a; return true; }
    Fp2 a1,x0,alpha,chk;
    fp2_pow(a1,a,PM3D4,6);        // a^((p-3)/4)
    fp2_mul(x0,a1,a);             // a^((p+1)/4)
    fp2_mul(alpha,a1,x0);         // a^((p-1)/2)
    Fp2 negone; fp2_neg(negone,FP2_ONE);
    if(fp2_eq(alpha,negone)){
        // x = i * x0
        Fp t=x0.c0; fp_neg(o.c0,x0.c1); o.c1=t;
    } else {
        Fp2 b,bp; fp2_add(b,alpha,FP2_ONE);
        fp2_pow(bp,b,PM1D2,6);
        fp2_mul(o,bp,x0);
    }
    fp2_sqr(chk,o);
    return fp2_eq(chk,a);
}
static int fp2_sgn0(const Fp2&a){
    Fp p0,p1; fp_from_mont(p0,a.c0); fp_from_mont(p1,a.c1);
    int s0=p0.l[0]&1, z0=fp_is_zero(p0)?1:0, s1=p1.l[0]&1;
    return s0 | (z0 & s1);
}
static bool fp2_lex_larger(const Fp2&a){
    if(!fp_is_zero(a.c1)) return fp_lex_larger(a.c1);
    return fp_lex_larger(a.c0);
}

// ---------------------------------------------------------------------------
// Fp12 = Fp2[w]/(w^6 - xi), coefficients low-degree-first
// ---------------------------------------------------------------------------
struct Fp12 { Fp2 c[6]; };
static Fp12 FP12_ONE;
static Fp2 FROB_G[6];   // gamma_j = xi^(j*(p-1)/6), for f -> f^p

static inline bool fp12_is_one(const Fp12&a){
    if(!fp2_eq(a.c[0],FP2_ONE)) return false;
    for(int j=1;j<6;j++) if(!fp2_is_zero(a.c[j])) return false;
    return true;
}
static void fp12_mul(Fp12&o,const Fp12&a,const Fp12&b){
    Fp2 acc[11]; for(int k=0;k<11;k++) acc[k]=FP2_ZERO;
    Fp2 t;
    for(int i=0;i<6;i++) for(int j=0;j<6;j++){
        fp2_mul(t,a.c[i],b.c[j]); fp2_add(acc[i+j],acc[i+j],t);
    }
    Fp12 r;
    for(int k=0;k<6;k++){
        r.c[k]=acc[k];
        if(k+6<11){ Fp2 hi; fp2_mul_xi(hi,acc[k+6]); fp2_add(r.c[k],r.c[k],hi); }
    }
    o=r;
}
static void fp12_sqr(Fp12&o,const Fp12&a){ fp12_mul(o,a,a); }
static void fp12_frob(Fp12&o,const Fp12&a){     // f -> f^p
    for(int j=0;j<6;j++){ Fp2 cj; fp2_conj(cj,a.c[j]); fp2_mul(o.c[j],cj,FROB_G[j]); }
}
static void fp12_frobk(Fp12&o,const Fp12&a,int k){
    Fp12 r=a; for(int i=0;i<k;i++) fp12_frob(r,r); o=r;
}
static void fp12_conj6(Fp12&o,const Fp12&a){ fp12_frobk(o,a,6); }  // f^(p^6)
// Fp6-view inversion: f = A + wB, A=(c0,c2,c4), B=(c1,c3,c5) over v=w^2, v^3=xi
struct Fp6v { Fp2 a,b,c; };
static void fp6_mul(Fp6v&o,const Fp6v&x,const Fp6v&y){
    Fp2 aa,bb,cc,t1,t2,t3,tmp;
    fp2_mul(aa,x.a,y.a); fp2_mul(bb,x.b,y.b); fp2_mul(cc,x.c,y.c);
    // c0 = aa + xi*((b+c)(yb+yc) - bb - cc)
    Fp2 s1,s2; fp2_add(s1,x.b,x.c); fp2_add(s2,y.b,y.c); fp2_mul(t1,s1,s2);
    fp2_sub(t1,t1,bb); fp2_sub(t1,t1,cc); fp2_mul_xi(tmp,t1); fp2_add(t1,aa,tmp);
    // c1 = (a+b)(ya+yb) - aa - bb + xi*cc
    fp2_add(s1,x.a,x.b); fp2_add(s2,y.a,y.b); fp2_mul(t2,s1,s2);
    fp2_sub(t2,t2,aa); fp2_sub(t2,t2,bb); fp2_mul_xi(tmp,cc); fp2_add(t2,t2,tmp);
    // c2 = (a+c)(ya+yc) - aa - cc + bb
    fp2_add(s1,x.a,x.c); fp2_add(s2,y.a,y.c); fp2_mul(t3,s1,s2);
    fp2_sub(t3,t3,aa); fp2_sub(t3,t3,cc); fp2_add(t3,t3,bb);
    o.a=t1; o.b=t2; o.c=t3;
}
static void fp6_inv(Fp6v&o,const Fp6v&x){
    Fp2 A,B,C,t,xi_t;
    fp2_sqr(A,x.a); fp2_mul(t,x.b,x.c); fp2_mul_xi(xi_t,t); fp2_sub(A,A,xi_t);      // a^2 - xi*b*c
    fp2_sqr(B,x.c); fp2_mul_xi(B,B); fp2_mul(t,x.a,x.b); fp2_sub(B,B,t);            // xi*c^2 - a*b
    fp2_sqr(C,x.b); fp2_mul(t,x.a,x.c); fp2_sub(C,C,t);                              // b^2 - a*c
    Fp2 F,f1,f2;
    fp2_mul(f1,x.c,B); fp2_mul(f2,x.b,C); fp2_add(F,f1,f2); fp2_mul_xi(F,F);
    fp2_mul(f1,x.a,A); fp2_add(F,F,f1);                                              // norm
    Fp2 Finv; fp2_inv(Finv,F);
    fp2_mul(o.a,A,Finv); fp2_mul(o.b,B,Finv); fp2_mul(o.c,C,Finv);
}
static void fp12_inv(Fp12&o,const Fp12&x){
    Fp6v A={x.c[0],x.c[2],x.c[4]}, B={x.c[1],x.c[3],x.c[5]};
    // (A+wB)^-1 = (A - wB) / (A^2 - v*B^2)   [w^2 = v]
    Fp6v A2,B2,vB2,D,Dinv,ra,rb;
    fp6_mul(A2,A,A); fp6_mul(B2,B,B);
    // v*B2: (a,b,c) -> (xi*c, a, b)
    fp2_mul_xi(vB2.a,B2.c); vB2.b=B2.a; vB2.c=B2.b;
    fp2_sub(D.a,A2.a,vB2.a); fp2_sub(D.b,A2.b,vB2.b); fp2_sub(D.c,A2.c,vB2.c);
    fp6_inv(Dinv,D);
    fp6_mul(ra,A,Dinv); fp6_mul(rb,B,Dinv);
    o.c[0]=ra.a; o.c[2]=ra.b; o.c[4]=ra.c;
    fp2_neg(o.c[1],rb.a); fp2_neg(o.c[3],rb.b); fp2_neg(o.c[5],rb.c);
}

// ---------------------------------------------------------------------------
// Curve points (jacobian): G1 over Fp (y^2=x^3+4), G2 over Fp2 (y^2=x^3+4xi)
// ---------------------------------------------------------------------------
struct G1 { Fp x,y,z; };     // z==0 => infinity
struct G2 { Fp2 x,y,z; };
static Fp B1_M;              // 4 (mont)
static Fp2 B2_M;             // 4+4i (mont)
static G1 G1_GEN; static G2 G2_GEN;
static u64 R_LIMBS[4] = {0xffffffff00000001ULL, 0x53bda402fffe5bfeULL,
                         0x3339d80809a1d805ULL, 0x73eda753299d7d48ULL};
static const u64 U_ABS = 0xd201000000010000ULL;   // |x| (BLS parameter, x<0)

static inline bool g1_is_inf(const G1&p){ return fp_is_zero(p.z); }
static inline bool g2_is_inf(const G2&p){ return fp2_is_zero(p.z); }
static void g1_dbl(G1&o,const G1&p){
    if(g1_is_inf(p)){ o=p; return; }
    Fp a,b,c,d,e,f,t;
    fp_sqr(a,p.x); fp_sqr(b,p.y); fp_sqr(c,b);
    fp_add(d,p.x,b); fp_sqr(d,d); fp_sub(d,d,a); fp_sub(d,d,c); fp_add(d,d,d); // 2((x+b)^2-a-c)
    fp_add(e,a,a); fp_add(e,e,a);                                              // 3a
    fp_sqr(f,e);
    fp_sub(o.x,f,d); fp_sub(o.x,o.x,d);
    fp_sub(t,d,o.x); fp_mul(t,e,t);
    Fp c8; fp_add(c8,c,c); fp_add(c8,c8,c8); fp_add(c8,c8,c8);
    fp_sub(t,t,c8);
    Fp yz; fp_mul(yz,p.y,p.z);
    fp_add(o.z,yz,yz);
    o.y=t;
}
static void g1_add(G1&o,const G1&p,const G1&q){
    if(g1_is_inf(p)){ o=q; return; }
    if(g1_is_inf(q)){ o=p; return; }
    Fp z1z1,z2z2,u1,u2,s1,s2,h,i,j,rr,v,t;
    fp_sqr(z1z1,p.z); fp_sqr(z2z2,q.z);
    fp_mul(u1,p.x,z2z2); fp_mul(u2,q.x,z1z1);
    fp_mul(s1,p.y,q.z); fp_mul(s1,s1,z2z2);
    fp_mul(s2,q.y,p.z); fp_mul(s2,s2,z1z1);
    if(fp_cmp(u1,u2)==0){
        if(fp_cmp(s1,s2)==0){ g1_dbl(o,p); return; }
        o.x=FP_ONE_M; o.y=FP_ONE_M; o.z=FP_ZERO; return;  // infinity
    }
    fp_sub(h,u2,u1);
    fp_add(i,h,h); fp_sqr(i,i);
    fp_mul(j,h,i);
    fp_sub(rr,s2,s1); fp_add(rr,rr,rr);
    fp_mul(v,u1,i);
    Fp r2; fp_sqr(r2,rr);
    fp_sub(o.x,r2,j); fp_sub(o.x,o.x,v); fp_sub(o.x,o.x,v);
    fp_sub(t,v,o.x); fp_mul(t,rr,t);
    Fp s1j; fp_mul(s1j,s1,j); fp_add(s1j,s1j,s1j);
    fp_sub(o.y,t,s1j);
    fp_add(t,p.z,q.z); fp_sqr(t,t); fp_sub(t,t,z1z1); fp_sub(t,t,z2z2);
    fp_mul(o.z,t,h);
}
static void g2_dbl(G2&o,const G2&p){
    if(g2_is_inf(p)){ o=p; return; }
    Fp2 a,b,c,d,e,f,t;
    fp2_sqr(a,p.x); fp2_sqr(b,p.y); fp2_sqr(c,b);
    fp2_add(d,p.x,b); fp2_sqr(d,d); fp2_sub(d,d,a); fp2_sub(d,d,c); fp2_add(d,d,d);
    fp2_add(e,a,a); fp2_add(e,e,a);
    fp2_sqr(f,e);
    fp2_sub(o.x,f,d); fp2_sub(o.x,o.x,d);
    fp2_sub(t,d,o.x); fp2_mul(t,e,t);
    Fp2 c8; fp2_add(c8,c,c); fp2_add(c8,c8,c8); fp2_add(c8,c8,c8);
    fp2_sub(t,t,c8);
    Fp2 yz; fp2_mul(yz,p.y,p.z);
    fp2_add(o.z,yz,yz);
    o.y=t;
}
static void g2_add(G2&o,const G2&p,const G2&q){
    if(g2_is_inf(p)){ o=q; return; }
    if(g2_is_inf(q)){ o=p; return; }
    Fp2 z1z1,z2z2,u1,u2,s1,s2,h,i,j,rr,v,t;
    fp2_sqr(z1z1,p.z); fp2_sqr(z2z2,q.z);
    fp2_mul(u1,p.x,z2z2); fp2_mul(u2,q.x,z1z1);
    fp2_mul(s1,p.y,q.z); fp2_mul(s1,s1,z2z2);
    fp2_mul(s2,q.y,p.z); fp2_mul(s2,s2,z1z1);
    if(fp2_eq(u1,u2)){
        if(fp2_eq(s1,s2)){ g2_dbl(o,p); return; }
        o.x=FP2_ONE; o.y=FP2_ONE; o.z=FP2_ZERO; return;
    }
    fp2_sub(h,u2,u1);
    fp2_add(i,h,h); fp2_sqr(i,i);
    fp2_mul(j,h,i);
    fp2_sub(rr,s2,s1); fp2_add(rr,rr,rr);
    fp2_mul(v,u1,i);
    Fp2 r2; fp2_sqr(r2,rr);
    fp2_sub(o.x,r2,j); fp2_sub(o.x,o.x,v); fp2_sub(o.x,o.x,v);
    fp2_sub(t,v,o.x); fp2_mul(t,rr,t);
    Fp2 s1j; fp2_mul(s1j,s1,j); fp2_add(s1j,s1j,s1j);
    fp2_sub(o.y,t,s1j);
    fp2_add(t,p.z,q.z); fp2_sqr(t,t); fp2_sub(t,t,z1z1); fp2_sub(t,t,z2z2);
    fp2_mul(o.z,t,h);
}
static void g1_neg(G1&o,const G1&p){ o=p; fp_neg(o.y,p.y); }
static void g2_neg(G2&o,const G2&p){ o=p; fp2_neg(o.y,p.y); }
// scalar mul, scalar as big-endian byte array
static void g1_mul(G1&o,const G1&p,const u8*k,int klen){
    G1 r={FP_ONE_M,FP_ONE_M,FP_ZERO};
    for(int i=0;i<klen;i++){
        for(int b=7;b>=0;b--){
            g1_dbl(r,r);
            if((k[i]>>b)&1) g1_add(r,r,p);
        }
    }
    o=r;
}
static void g2_mul(G2&o,const G2&p,const u8*k,int klen){
    G2 r={FP2_ONE,FP2_ONE,FP2_ZERO};
    for(int i=0;i<klen;i++){
        for(int b=7;b>=0;b--){
            g2_dbl(r,r);
            if((k[i]>>b)&1) g2_add(r,r,p);
        }
    }
    o=r;
}
static void g1_to_affine(Fp&x,Fp&y,const G1&p){
    Fp zi,zi2,zi3; fp_inv(zi,p.z); fp_sqr(zi2,zi); fp_mul(zi3,zi2,zi);
    fp_mul(x,p.x,zi2); fp_mul(y,p.y,zi3);
}
static void g2_to_affine(Fp2&x,Fp2&y,const G2&p){
    Fp2 zi,zi2,zi3; fp2_inv(zi,p.z); fp2_sqr(zi2,zi); fp2_mul(zi3,zi2,zi);
    fp2_mul(x,p.x,zi2); fp2_mul(y,p.y,zi3);
}
static bool g1_on_curve(const G1&p){
    if(g1_is_inf(p)) return true;
    Fp x,y,l,r; g1_to_affine(x,y,p);
    fp_sqr(l,y); fp_sqr(r,x); fp_mul(r,r,x); fp_add(r,r,B1_M);
    return fp_cmp(l,r)==0;
}
static bool g2_on_curve(const G2&p){
    if(g2_is_inf(p)) return true;
    Fp2 x,y,l,r; g2_to_affine(x,y,p);
    fp2_sqr(l,y); fp2_sqr(r,x); fp2_mul(r,r,x); fp2_add(r,r,B2_M);
    return fp2_eq(l,r);
}
static u8 R_BYTES_BE[32];
// G1 membership by the endomorphism sigma(x,y) = (beta x, y), with beta =
// 2^((p-1)/3) mod p, the cube root of unity on which sigma acts on G1 as
// [-u^2]: P is in G1 iff sigma(P) + [u^2]P = O (Bowe, "Faster subgroup
// checks for BLS12-381"; u^4 - u^2 + 1 = r), a 128-bit ladder in place of
// r's 255 bits.  bls_selftest fails if beta does not fix the generator.
static const Fp G1_BETA_PLAIN={{0x2e01fffffffefffeULL,0xde17d813620a0002ULL,
    0xddb3a93be6f89688ULL,0xba69c6076a0f77eaULL,0x5f19672fdf76ce51ULL,0}};
static Fp G1_BETA;                 // Montgomery form, set at init
static u8 U2_BYTES_BE[16];         // u^2, big-endian
static bool g1_in_subgroup(const G1&p){
    G1 s=p; fp_mul(s.x,p.x,G1_BETA);
    G1 t,o; g1_mul(t,p,U2_BYTES_BE,16);
    g1_add(o,s,t);
    return g1_is_inf(o);
}
static bool g2_in_subgroup_slow(const G2&p){ G2 t; g2_mul(t,p,R_BYTES_BE,32); return g2_is_inf(t); }

// psi endomorphism on the twist: psi(x,y) = (PSI_CX * conj(x), PSI_CY * conj(y))
// (untwist o frobenius o twist; constants derived at init from gamma =
// xi^((p-1)/6)).  On G2 psi acts as [u]; used for the fast subgroup check
// psi(Q) == [u]Q and Budroni-Pintore cofactor clearing — both RUNTIME-
// VERIFIED against the slow mul-by-r/h_eff paths at init (USE_FAST_G2).
static Fp2 PSI_CX, PSI_CY;
static bool USE_FAST_SUBGROUP=false, USE_FAST_COFACTOR=false;
static void g2_psi_affine(Fp2&ox,Fp2&oy,const Fp2&x,const Fp2&y){
    Fp2 cx,cy; fp2_conj(cx,x); fp2_conj(cy,y);
    fp2_mul(ox,cx,PSI_CX); fp2_mul(oy,cy,PSI_CY);
}
static void g2_psi(G2&o,const G2&p){
    if(g2_is_inf(p)){ o=p; return; }
    Fp2 x,y; g2_to_affine(x,y,p);
    Fp2 px,py; g2_psi_affine(px,py,x,y);
    o.x=px; o.y=py; o.z=FP2_ONE;
}
static void g2_mul_u64(G2&o,const G2&p,u64 k){
    u8 kb[8]; for(int i=0;i<8;i++) kb[i]=(u8)(k>>(56-8*i));
    g2_mul(o,p,kb,8);
}
static bool g2_eq(const G2&a,const G2&b){     // jacobian equality
    if(g2_is_inf(a)||g2_is_inf(b)) return g2_is_inf(a)&&g2_is_inf(b);
    Fp2 za2,zb2,za3,zb3,l,r;
    fp2_sqr(za2,a.z); fp2_sqr(zb2,b.z);
    fp2_mul(l,a.x,zb2); fp2_mul(r,b.x,za2);
    if(!fp2_eq(l,r)) return false;
    fp2_mul(za3,za2,a.z); fp2_mul(zb3,zb2,b.z);
    fp2_mul(l,a.y,zb3); fp2_mul(r,b.y,za3);
    return fp2_eq(l,r);
}
static bool g2_in_subgroup(const G2&p){
    if(g2_is_inf(p)) return true;
    if(!USE_FAST_SUBGROUP) return g2_in_subgroup_slow(p);
    // psi(Q) == [u]Q, u < 0: psi(Q) == -[|u|]Q
    G2 psi_q,uq; g2_psi(psi_q,p);
    g2_mul_u64(uq,p,U_ABS); g2_neg(uq,uq);
    return g2_eq(psi_q,uq);
}
static void g2_clear_cofactor_slow(G2&o,const G2&p);
static void g2_clear_cofactor(G2&o,const G2&p){
    if(!USE_FAST_COFACTOR){ g2_clear_cofactor_slow(o,p); return; }
    // Budroni-Pintore: h_eff*Q = [u^2-u-1]Q + [u-1]psi(Q) + psi^2([2]Q)
    // with u<0: u^2-u-1 = U^2+U-1 (U=|u|), [u-1]Q = -[U+1]Q
    G2 t1,t2,t3,acc;
    // [U^2+U-1]Q: 16-byte big-endian scalar
    u128 k=(u128)U_ABS*U_ABS+U_ABS-1;
    u8 kb[16]; for(int i=0;i<16;i++) kb[i]=(u8)(k>>(120-8*i));
    g2_mul(t1,p,kb,16);
    G2 up1; g2_mul_u64(up1,p,U_ABS+1); g2_neg(up1,up1);   // [u-1]Q... [-(U+1)]Q
    g2_psi(t2,up1);
    G2 two_q; g2_dbl(two_q,p);
    g2_psi(t3,two_q); g2_psi(t3,t3);
    g2_add(acc,t1,t2); g2_add(o,acc,t3);
}

// ---------------------------------------------------------------------------
// zcash-format (de)compression
// ---------------------------------------------------------------------------
static bool g1_decompress(G1&o,const u8*in){      // 48 bytes; no subgroup check
    if(!(in[0]&0x80)) return false;
    if(in[0]&0x40){                                // infinity
        for(int i=0;i<48;i++) if((i==0?in[0]&0x3f:in[i])!=0) return false;
        o.x=FP_ONE_M; o.y=FP_ONE_M; o.z=FP_ZERO; return true;
    }
    u8 buf[48]; memcpy(buf,in,48); buf[0]&=0x1f;
    Fp xp; fp_from_be(xp,buf);
    if(fp_cmp(xp,*(const Fp*)P_LIMBS)>=0) return false;
    Fp x; fp_to_mont(x,xp);
    Fp rhs,y; fp_sqr(rhs,x); fp_mul(rhs,rhs,x); fp_add(rhs,rhs,B1_M);
    if(!fp_sqrt(y,rhs)) return false;
    bool want_larger=(in[0]&0x20)!=0;
    if(fp_lex_larger(y)!=want_larger) fp_neg(y,y);
    o.x=x; o.y=y; o.z=FP_ONE_M;
    return true;
}
static bool g2_decompress(G2&o,const u8*in){      // 96 bytes: x.c1 || x.c0
    if(!(in[0]&0x80)) return false;
    if(in[0]&0x40){
        for(int i=0;i<96;i++) if((i==0?in[0]&0x3f:in[i])!=0) return false;
        o.x=FP2_ONE; o.y=FP2_ONE; o.z=FP2_ZERO; return true;
    }
    u8 buf[48]; memcpy(buf,in,48); buf[0]&=0x1f;
    Fp c1p,c0p; fp_from_be(c1p,buf); fp_from_be(c0p,in+48);
    if(fp_cmp(c1p,*(const Fp*)P_LIMBS)>=0) return false;
    if(fp_cmp(c0p,*(const Fp*)P_LIMBS)>=0) return false;
    Fp2 x; fp_to_mont(x.c0,c0p); fp_to_mont(x.c1,c1p);
    Fp2 rhs,y; fp2_sqr(rhs,x); fp2_mul(rhs,rhs,x); fp2_add(rhs,rhs,B2_M);
    if(!fp2_sqrt(y,rhs)) return false;
    bool want_larger=(in[0]&0x20)!=0;
    if(fp2_lex_larger(y)!=want_larger) fp2_neg(y,y);
    o.x=x; o.y=y; o.z=FP2_ONE;
    return true;
}
static void g1_compress(u8*out,const G1&p){
    if(g1_is_inf(p)){ memset(out,0,48); out[0]=0xC0; return; }
    Fp x,y; g1_to_affine(x,y,p);
    Fp xp; fp_from_mont(xp,x); fp_to_be(out,xp);
    out[0]|=0x80; if(fp_lex_larger(y)) out[0]|=0x20;
}
static void g2_compress(u8*out,const G2&p){
    if(g2_is_inf(p)){ memset(out,0,96); out[0]=0xC0; return; }
    Fp2 x,y; g2_to_affine(x,y,p);
    Fp c1p,c0p; fp_from_mont(c1p,x.c1); fp_from_mont(c0p,x.c0);
    fp_to_be(out,c1p); fp_to_be(out+48,c0p);
    out[0]|=0x80; if(fp2_lex_larger(y)) out[0]|=0x20;
}

// ---------------------------------------------------------------------------
// Multi-pairing: affine Miller loop with batch inversion, line slots {0,3,5}
// ---------------------------------------------------------------------------
struct PairAff { Fp px,py; Fp2 qx,qy; Fp2 tx,ty; bool inf; };

static void fp12_mul_line(Fp12&f,const Fp2&s0,const Fp2&s3,const Fp2&s5){
    Fp2 t,acc[6];
    // r0 = f0*s0 + xi*(f3*s3) + xi*(f1*s5)
    fp2_mul(acc[0],f.c[0],s0);
    fp2_mul(t,f.c[3],s3); fp2_mul_xi(t,t); fp2_add(acc[0],acc[0],t);
    fp2_mul(t,f.c[1],s5); fp2_mul_xi(t,t); fp2_add(acc[0],acc[0],t);
    // r1 = f1*s0 + xi*(f4*s3) + xi*(f2*s5)
    fp2_mul(acc[1],f.c[1],s0);
    fp2_mul(t,f.c[4],s3); fp2_mul_xi(t,t); fp2_add(acc[1],acc[1],t);
    fp2_mul(t,f.c[2],s5); fp2_mul_xi(t,t); fp2_add(acc[1],acc[1],t);
    // r2 = f2*s0 + xi*(f5*s3) + xi*(f3*s5)
    fp2_mul(acc[2],f.c[2],s0);
    fp2_mul(t,f.c[5],s3); fp2_mul_xi(t,t); fp2_add(acc[2],acc[2],t);
    fp2_mul(t,f.c[3],s5); fp2_mul_xi(t,t); fp2_add(acc[2],acc[2],t);
    // r3 = f3*s0 + f0*s3 + xi*(f4*s5)
    fp2_mul(acc[3],f.c[3],s0);
    fp2_mul(t,f.c[0],s3); fp2_add(acc[3],acc[3],t);
    fp2_mul(t,f.c[4],s5); fp2_mul_xi(t,t); fp2_add(acc[3],acc[3],t);
    // r4 = f4*s0 + f1*s3 + xi*(f5*s5)
    fp2_mul(acc[4],f.c[4],s0);
    fp2_mul(t,f.c[1],s3); fp2_add(acc[4],acc[4],t);
    fp2_mul(t,f.c[5],s5); fp2_mul_xi(t,t); fp2_add(acc[4],acc[4],t);
    // r5 = f5*s0 + f2*s3 + f0*s5
    fp2_mul(acc[5],f.c[5],s0);
    fp2_mul(t,f.c[2],s3); fp2_add(acc[5],acc[5],t);
    fp2_mul(t,f.c[0],s5); fp2_add(acc[5],acc[5],t);
    for(int j=0;j<6;j++) f.c[j]=acc[j];
}
// batch-invert dens[0..n) in place (Montgomery trick)
static void fp2_batch_inv(Fp2*dens,int n){
    std::vector<Fp2> pref(n);
    Fp2 acc=FP2_ONE;
    for(int i=0;i<n;i++){ pref[i]=acc; fp2_mul(acc,acc,dens[i]); }
    Fp2 inv; fp2_inv(inv,acc);
    for(int i=n-1;i>=0;i--){
        Fp2 t; fp2_mul(t,inv,pref[i]);
        fp2_mul(inv,inv,dens[i]);
        dens[i]=t;
    }
}
// one Miller step kind: 0=double, 1=add Q.  dens prefilled with denominators.
static void miller_lines(Fp12&f,std::vector<PairAff>&ps,int kind){
    int n=(int)ps.size();
    std::vector<Fp2> dens(n);
    for(int i=0;i<n;i++){
        if(ps[i].inf){ dens[i]=FP2_ONE; continue; }
        if(kind==0){ fp2_add(dens[i],ps[i].ty,ps[i].ty); }          // 2y
        else       { fp2_sub(dens[i],ps[i].qx,ps[i].tx); }          // xQ - xT
    }
    fp2_batch_inv(dens.data(),n);
    for(int i=0;i<n;i++){
        if(ps[i].inf) continue;
        PairAff&pr=ps[i];
        Fp2 lam,num;
        if(kind==0){ Fp2 x2; fp2_sqr(x2,pr.tx); fp2_add(num,x2,x2); fp2_add(num,num,x2); }
        else       { fp2_sub(num,pr.qy,pr.ty); }
        fp2_mul(lam,num,dens[i]);
        // line slots: s0 = xi*yP (Fp2 (yP,yP)), s3 = lam*xT - yT, s5 = -lam*xP
        Fp2 s0; s0.c0=pr.py; s0.c1=pr.py;
        Fp2 s3; fp2_mul(s3,lam,pr.tx); fp2_sub(s3,s3,pr.ty);
        Fp2 s5; fp2_mul_fp(s5,lam,pr.px); fp2_neg(s5,s5);
        fp12_mul_line(f,s0,s3,s5);
        // advance T
        Fp2 nx,ny,t;
        if(kind==0){
            fp2_sqr(nx,lam); fp2_sub(nx,nx,pr.tx); fp2_sub(nx,nx,pr.tx);
        } else {
            fp2_sqr(nx,lam); fp2_sub(nx,nx,pr.tx); fp2_sub(nx,nx,pr.qx);
        }
        fp2_sub(t,pr.tx,nx); fp2_mul(ny,lam,t); fp2_sub(ny,ny,pr.ty);
        pr.tx=nx; pr.ty=ny;
    }
}
// product of miller loops over pairs (P_i affine mont, Q_i affine mont)
static void multi_miller(Fp12&f,std::vector<PairAff>&ps){
    f=FP12_ONE;
    for(int bit=62;bit>=0;bit--){            // |u| top bit is 63; start below it
        fp12_sqr(f,f);
        miller_lines(f,ps,0);
        if((U_ABS>>bit)&1) miller_lines(f,ps,1);
    }
    fp12_conj6(f,f);                          // u < 0
}

// ---------------------------------------------------------------------------
// Final exponentiation (3d variant, see header comment)
// ---------------------------------------------------------------------------
static void fp12_pow_uabs(Fp12&o,const Fp12&a){
    Fp12 r=a;
    for(int bit=62;bit>=0;bit--){
        fp12_sqr(r,r);
        if((U_ABS>>bit)&1) fp12_mul(r,r,a);
    }
    o=r;
}
static void fp12_pow_u(Fp12&o,const Fp12&a){     // a^u, a cyclotomic, u<0
    Fp12 t; fp12_pow_uabs(t,a); fp12_conj6(o,t);
}
static bool pairing_product_is_one(const Fp12&f){
    Fp12 m,c,fi,t;
    fp12_conj6(c,f); fp12_inv(fi,f); fp12_mul(m,c,fi);     // f^(p^6-1)
    fp12_frobk(t,m,2); fp12_mul(m,t,m);                     // ^(p^2+1): now cyclotomic
    // A2 = m^((u-1)^2):  x^(u-1) = x^u * conj6(x)
    Fp12 a,cj;
    fp12_pow_u(a,m); fp12_conj6(cj,m); fp12_mul(a,a,cj);    // m^(u-1)
    Fp12 a2; fp12_pow_u(a2,a); fp12_conj6(cj,a); fp12_mul(a2,a2,cj);
    // B = A2^(u+p)
    Fp12 b,fr; fp12_pow_u(b,a2); fp12_frob(fr,a2); fp12_mul(b,b,fr);
    // C = B^(u^2+p^2-1) = (B^u)^u * frob2(B) * conj6(B)
    Fp12 bu,buu; fp12_pow_u(bu,b); fp12_pow_u(buu,bu);
    fp12_frobk(fr,b,2); fp12_mul(buu,buu,fr);
    fp12_conj6(cj,b); fp12_mul(buu,buu,cj);
    // out = C * m^3
    Fp12 m2,m3; fp12_sqr(m2,m); fp12_mul(m3,m2,m);
    Fp12 out; fp12_mul(out,buu,m3);
    return fp12_is_one(out);
}

// ---------------------------------------------------------------------------
// SHA-256 (compact) + expand_message_xmd + hash_to_field
// ---------------------------------------------------------------------------
struct Sha256 {
    uint32_t h[8]; u64 len; u8 buf[64]; int fill;
    static uint32_t rotr(uint32_t x,int n){ return (x>>n)|(x<<(32-n)); }
    void init(){
        static const uint32_t iv[8]={0x6a09e667,0xbb67ae85,0x3c6ef372,0xa54ff53a,
                                     0x510e527f,0x9b05688c,0x1f83d9ab,0x5be0cd19};
        memcpy(h,iv,32); len=0; fill=0;
    }
    void compress(const u8*p){
        static const uint32_t K[64]={
            0x428a2f98,0x71374491,0xb5c0fbcf,0xe9b5dba5,0x3956c25b,0x59f111f1,
            0x923f82a4,0xab1c5ed5,0xd807aa98,0x12835b01,0x243185be,0x550c7dc3,
            0x72be5d74,0x80deb1fe,0x9bdc06a7,0xc19bf174,0xe49b69c1,0xefbe4786,
            0x0fc19dc6,0x240ca1cc,0x2de92c6f,0x4a7484aa,0x5cb0a9dc,0x76f988da,
            0x983e5152,0xa831c66d,0xb00327c8,0xbf597fc7,0xc6e00bf3,0xd5a79147,
            0x06ca6351,0x14292967,0x27b70a85,0x2e1b2138,0x4d2c6dfc,0x53380d13,
            0x650a7354,0x766a0abb,0x81c2c92e,0x92722c85,0xa2bfe8a1,0xa81a664b,
            0xc24b8b70,0xc76c51a3,0xd192e819,0xd6990624,0xf40e3585,0x106aa070,
            0x19a4c116,0x1e376c08,0x2748774c,0x34b0bcb5,0x391c0cb3,0x4ed8aa4a,
            0x5b9cca4f,0x682e6ff3,0x748f82ee,0x78a5636f,0x84c87814,0x8cc70208,
            0x90befffa,0xa4506ceb,0xbef9a3f7,0xc67178f2};
        uint32_t w[64];
        for(int i=0;i<16;i++) w[i]=(p[4*i]<<24)|(p[4*i+1]<<16)|(p[4*i+2]<<8)|p[4*i+3];
        for(int i=16;i<64;i++){
            uint32_t s0=rotr(w[i-15],7)^rotr(w[i-15],18)^(w[i-15]>>3);
            uint32_t s1=rotr(w[i-2],17)^rotr(w[i-2],19)^(w[i-2]>>10);
            w[i]=w[i-16]+s0+w[i-7]+s1;
        }
        uint32_t a=h[0],b=h[1],c=h[2],d=h[3],e=h[4],f=h[5],g=h[6],hh=h[7];
        for(int i=0;i<64;i++){
            uint32_t S1=rotr(e,6)^rotr(e,11)^rotr(e,25);
            uint32_t ch=(e&f)^((~e)&g);
            uint32_t t1=hh+S1+ch+K[i]+w[i];
            uint32_t S0=rotr(a,2)^rotr(a,13)^rotr(a,22);
            uint32_t mj=(a&b)^(a&c)^(b&c);
            uint32_t t2=S0+mj;
            hh=g; g=f; f=e; e=d+t1; d=c; c=b; b=a; a=t1+t2;
        }
        h[0]+=a;h[1]+=b;h[2]+=c;h[3]+=d;h[4]+=e;h[5]+=f;h[6]+=g;h[7]+=hh;
    }
    void update(const u8*p,size_t n){
        len+=n;
        while(n){
            size_t take=64-fill; if(take>n) take=n;
            memcpy(buf+fill,p,take); fill+=(int)take; p+=take; n-=take;
            if(fill==64){ compress(buf); fill=0; }
        }
    }
    void final(u8*out){
        u64 bits=len*8;
        u8 pad=0x80; update(&pad,1);
        u8 z=0; while(fill!=56) update(&z,1);
        u8 lb[8]; for(int i=0;i<8;i++) lb[i]=(u8)(bits>>(56-8*i));
        update(lb,8);
        for(int i=0;i<8;i++){ out[4*i]=(u8)(h[i]>>24); out[4*i+1]=(u8)(h[i]>>16);
                              out[4*i+2]=(u8)(h[i]>>8); out[4*i+3]=(u8)h[i]; }
    }
};
static void sha256(u8*out,const u8*a,size_t alen,const u8*b=nullptr,size_t blen=0,
                   const u8*c=nullptr,size_t clen=0){
    Sha256 s; s.init(); s.update(a,alen);
    if(b) s.update(b,blen); if(c) s.update(c,clen);
    s.final(out);
}
// RFC 9380 5.3.1, SHA-256
static void expand_message_xmd(u8*out,size_t len_out,const u8*msg,size_t msglen,
                               const u8*dst,size_t dstlen){
    u8 hashed_dst[32];
    if(dstlen>255){                       // RFC 9380 5.3.3 oversize DST
        static const char pre[]="H2C-OVERSIZE-DST-";
        sha256(hashed_dst,(const u8*)pre,17,dst,dstlen);
        dst=hashed_dst; dstlen=32;
    }
    u8 dstp[256+1]; size_t dl=dstlen;
    memcpy(dstp,dst,dstlen); dstp[dl]=(u8)dl;
    size_t ell=(len_out+31)/32;
    u8 zpad[64]; memset(zpad,0,64);
    u8 lib[3]={(u8)(len_out>>8),(u8)len_out,0};
    Sha256 s; s.init();
    s.update(zpad,64); s.update(msg,msglen); s.update(lib,3); s.update(dstp,dl+1);
    u8 b0[32]; s.final(b0);
    u8 bi[32]; u8 ctr=1;
    sha256(bi,b0,32,&ctr,1,dstp,dl+1);
    memcpy(out,bi,len_out<32?len_out:32);
    for(size_t i=2;i<=ell;i++){
        u8 x[32]; for(int j=0;j<32;j++) x[j]=b0[j]^bi[j];
        ctr=(u8)i;
        sha256(bi,x,32,&ctr,1,dstp,dl+1);
        size_t off=(i-1)*32, take=len_out-off<32?len_out-off:32;
        memcpy(out+off,bi,take);
    }
}
// reduce a 64-byte big-endian value mod p into Montgomery form:
// split hi/lo 32 bytes: v = hi*2^256 + lo; mont(v) = hi*R2*mont(2^256/R...)
static void fp_from_be64_mod(Fp&o,const u8*b){
    // v mod p via schoolbook: out = ((hi mod p) * 2^256 + lo) mod p
    Fp hi,lo;
    u8 pad[48]; memset(pad,0,48);
    memcpy(pad+16,b,32); fp_from_be(hi,pad);       // top 32 bytes < 2^256 < p ok
    memcpy(pad+16,b+32,32); fp_from_be(lo,pad);
    Fp him,lom; fp_to_mont(him,hi); fp_to_mont(lom,lo);
    Fp t; fp_mul(t,him,TWO256_M); fp_add(o,t,lom);
}

// ---------------------------------------------------------------------------
// SSWU on E'(A'=240i, B'=1012(1+i)), Z=-(2+i), + 3-isogeny to E (RFC 9380)
// ---------------------------------------------------------------------------
static Fp2 SSWU_A, SSWU_B, SSWU_Z;                  // mont
static Fp2 ISO_XN[4], ISO_XD[2], ISO_YN[4], ISO_YD[3];  // monic denoms implied
static u8 H_EFF_G2_BE[80];

static void sswu_map(Fp2&ox,Fp2&oy,const Fp2&u){
    Fp2 u2,zu2,tv1,x1,gx1;
    fp2_sqr(u2,u); fp2_mul(zu2,SSWU_Z,u2);
    Fp2 zu2sq; fp2_sqr(zu2sq,zu2);
    fp2_add(tv1,zu2sq,zu2);
    if(fp2_is_zero(tv1)){
        Fp2 za; fp2_mul(za,SSWU_Z,SSWU_A);
        Fp2 zainv; fp2_inv(zainv,za);
        fp2_mul(x1,SSWU_B,zainv);
    } else {
        Fp2 tinv,nb,ainv,t;
        fp2_inv(tinv,tv1);
        fp2_add(t,FP2_ONE,tinv);
        fp2_neg(nb,SSWU_B); fp2_inv(ainv,SSWU_A);
        fp2_mul(x1,nb,ainv); fp2_mul(x1,x1,t);
    }
    Fp2 x13,ax; fp2_sqr(gx1,x1); fp2_mul(gx1,gx1,x1);
    fp2_mul(ax,SSWU_A,x1); fp2_add(gx1,gx1,ax); fp2_add(gx1,gx1,SSWU_B);
    Fp2 x,y;
    if(fp2_is_square(gx1)){
        x=x1; fp2_sqrt(y,gx1);
    } else {
        Fp2 x2,gx2,ax2;
        fp2_mul(x2,zu2,x1);
        fp2_sqr(gx2,x2); fp2_mul(gx2,gx2,x2);
        fp2_mul(ax2,SSWU_A,x2); fp2_add(gx2,gx2,ax2); fp2_add(gx2,gx2,SSWU_B);
        x=x2; fp2_sqrt(y,gx2);
    }
    if(fp2_sgn0(u)!=fp2_sgn0(y)) fp2_neg(y,y);
    ox=x; oy=y;
}
// returns false => point at infinity (RFC 4.1 exceptional case)
static bool iso_map(Fp2&ox,Fp2&oy,const Fp2&x,const Fp2&y){
    Fp2 xn,xd,yn,yd,t;
    xn=ISO_XN[3]; for(int i=2;i>=0;i--){ fp2_mul(xn,xn,x); fp2_add(xn,xn,ISO_XN[i]); }
    xd=FP2_ONE;   for(int i=1;i>=0;i--){ fp2_mul(xd,xd,x); fp2_add(xd,xd,ISO_XD[i]); }
    yn=ISO_YN[3]; for(int i=2;i>=0;i--){ fp2_mul(yn,yn,x); fp2_add(yn,yn,ISO_YN[i]); }
    yd=FP2_ONE;   for(int i=2;i>=0;i--){ fp2_mul(yd,yd,x); fp2_add(yd,yd,ISO_YD[i]); }
    if(fp2_is_zero(xd)||fp2_is_zero(yd)) return false;
    Fp2 xdi,ydi; fp2_inv(xdi,xd); fp2_inv(ydi,yd);
    fp2_mul(ox,xn,xdi);
    fp2_mul(t,y,yn); fp2_mul(oy,t,ydi);
    return true;
}
static void map_to_curve_g2(G2&o,const Fp2&u){
    Fp2 xp,yp,x,y;
    sswu_map(xp,yp,u);
    if(!iso_map(x,y,xp,yp)){ o.x=FP2_ONE; o.y=FP2_ONE; o.z=FP2_ZERO; return; }
    o.x=x; o.y=y; o.z=FP2_ONE;
}
static void hash_to_g2(G2&o,const u8*msg,size_t msglen,const u8*dst,size_t dstlen){
    u8 uni[256];
    expand_message_xmd(uni,256,msg,msglen,dst,dstlen);
    Fp2 u0,u1;
    fp_from_be64_mod(u0.c0,uni);      fp_from_be64_mod(u0.c1,uni+64);
    fp_from_be64_mod(u1.c0,uni+128);  fp_from_be64_mod(u1.c1,uni+192);
    G2 q0,q1,s;
    map_to_curve_g2(q0,u0); map_to_curve_g2(q1,u1);
    g2_add(s,q0,q1);
    g2_clear_cofactor(o,s);
}
static void g2_clear_cofactor_slow(G2&o,const G2&p){
    g2_mul(o,p,H_EFF_G2_BE,80);
}

// ---------------------------------------------------------------------------
// init
// ---------------------------------------------------------------------------
static int hexval(char c){ return c<='9'?c-'0':(c|32)-'a'+10; }
static void bytes_from_hex(u8*out,size_t n,const char*hex){
    for(size_t i=0;i<n;i++) out[i]=(u8)((hexval(hex[2*i])<<4)|hexval(hex[2*i+1]));
}
static void bignum_sub_small(u64*o,const u64*a,u64 s,int n){
    u128 br=s;
    for(int i=0;i<n;i++){ u128 d=(u128)a[i]-(u64)br; o[i]=(u64)d; br=(d>>64)&1; }
}
static void bignum_shr(u64*o,const u64*a,int k,int n){
    for(int i=0;i<n;i++){
        u64 lo=a[i]>>k;
        u64 hi=(i+1<n && k)?(a[i+1]<<(64-k)):0;
        o[i]=lo|hi;
    }
}
static void bignum_div3(u64*o,const u64*a,int n){
    u128 rem=0;
    for(int i=n-1;i>=0;i--){ u128 cur=(rem<<64)|a[i]; o[i]=(u64)(cur/3); rem=cur%3; }
}
static bool INITED=false;
static void ensure_init(){
    if(INITED) return;
    // N0 = -p^{-1} mod 2^64 (Newton)
    u64 inv=1, p0=P_LIMBS[0];
    for(int i=0;i<6;i++) inv*=2-p0*inv;
    N0=(u64)(0-inv);
    // FP_ONE_M = 2^384 mod p by doubling 1; R2 = 2^768 mod p
    Fp one={{1,0,0,0,0,0}}; Fp t=one;
    for(int i=0;i<384;i++) fp_add(t,t,t);
    FP_ONE_M=t;
    for(int i=0;i<384;i++) fp_add(t,t,t);
    R2=t;
    { Fp s=FP_ONE_M; for(int i=0;i<256;i++) fp_add(s,s,s); TWO256_M=s; }
    FP2_ZERO.c0=FP_ZERO; FP2_ZERO.c1=FP_ZERO;
    FP2_ONE.c0=FP_ONE_M; FP2_ONE.c1=FP_ZERO;
    for(int j=0;j<6;j++) FP12_ONE.c[j]=FP2_ZERO;
    FP12_ONE.c[0]=FP2_ONE;
    // exponents
    u64 pp1[6]; u128 c=1;
    for(int i=0;i<6;i++){ c+=P_LIMBS[i]; pp1[i]=(u64)c; c>>=64; }
    bignum_sub_small(PM2,P_LIMBS,2,6);
    bignum_shr(PP1D4,pp1,2,6);
    u64 pm3[6]; bignum_sub_small(pm3,P_LIMBS,3,6); bignum_shr(PM3D4,pm3,2,6);
    u64 pm1[6]; bignum_sub_small(pm1,P_LIMBS,1,6); bignum_shr(PM1D2,pm1,1,6);
    u64 half[6]; bignum_shr(half,pm1,1,6); bignum_div3(PM1D6,half,6);
    // frobenius gammas: g = xi^((p-1)/6); FROB_G[j]=g^j
    Fp two,xw; // xi = 1+i mont
    Fp2 xi; xi.c0=FP_ONE_M; xi.c1=FP_ONE_M;
    Fp2 g; fp2_pow(g,xi,PM1D6,6);
    FROB_G[0]=FP2_ONE;
    for(int j=1;j<6;j++) fp2_mul(FROB_G[j],FROB_G[j-1],g);
    // curve constants
    Fp four={{4,0,0,0,0,0}}; fp_to_mont(B1_M,four);
    B2_M.c0=B1_M; B2_M.c1=B1_M;
    // r as big-endian bytes
    for(int i=0;i<4;i++) for(int j=0;j<8;j++)
        R_BYTES_BE[(3-i)*8+j]=(u8)(R_LIMBS[i]>>(56-8*j));
    // generators (plain hex, affine)
    static const char*G1X="17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac586c55e83ff97a1aeffb3af00adb22c6bb";
    static const char*G1Y="08b3f481e3aaa0f1a09e30ed741d8ae4fcf5e095d5d00af600db18cb2c04b3edd03cc744a2888ae40caa232946c5e7e1";
    static const char*G2X1="13e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049334cf11213945d57e5ac7d055d042b7e";
    static const char*G2X0="024aa2b2f08f0a91260805272dc51051c6e47ad4fa403b02b4510b647ae3d1770bac0326a805bbefd48056c8c121bdb8";
    static const char*G2Y1="0606c4a02ea734cc32acd2b02bc28b99cb3e287e85a763af267492ab572e99ab3f370d275cec1da1aaa9075ff05f79be";
    static const char*G2Y0="0ce5d527727d6e118cc9cdc6da2e351aadfd9baa8cbdd3a76d429a695160d12c923ac9cc3baca289e193548608b82801";
    u8 buf[48]; Fp v;
    bytes_from_hex(buf,48,G1X); fp_from_be(v,buf); fp_to_mont(G1_GEN.x,v);
    bytes_from_hex(buf,48,G1Y); fp_from_be(v,buf); fp_to_mont(G1_GEN.y,v);
    G1_GEN.z=FP_ONE_M;
    bytes_from_hex(buf,48,G2X0); fp_from_be(v,buf); fp_to_mont(G2_GEN.x.c0,v);
    bytes_from_hex(buf,48,G2X1); fp_from_be(v,buf); fp_to_mont(G2_GEN.x.c1,v);
    bytes_from_hex(buf,48,G2Y0); fp_from_be(v,buf); fp_to_mont(G2_GEN.y.c0,v);
    bytes_from_hex(buf,48,G2Y1); fp_from_be(v,buf); fp_to_mont(G2_GEN.y.c1,v);
    G2_GEN.z=FP2_ONE;
    // G2 effective cofactor (derived in crypto/bls12_381/curve.py), 507 bits
    // RFC 9380 8.8.2 h_eff (derived in curve.py: h2 * (s_bp * h2^-1 mod r))
    static const char*HEFF="0bc69f08f2ee75b3584c6a0ea91b352888e2a8e9145ad7689986ff031508ffe1329c2f178731db956d82bf015d1212b02ec0ec69d7477c1ae954cbc06689f6a359894c0adebbf6b4e8020005aaa95551";
    bytes_from_hex(H_EFF_G2_BE,80,HEFF);
    // SSWU curve E' and Z
    Fp f240={{240,0,0,0,0,0}}, f1012={{1012,0,0,0,0,0}};
    SSWU_A.c0=FP_ZERO; fp_to_mont(SSWU_A.c1,f240);
    fp_to_mont(SSWU_B.c0,f1012); SSWU_B.c1=SSWU_B.c0;
    Fp two_={{2,0,0,0,0,0}}, one_={{1,0,0,0,0,0}}; Fp m2,m1;
    fp_to_mont(m2,two_); fp_to_mont(m1,one_);
    fp_neg(SSWU_Z.c0,m2); fp_neg(SSWU_Z.c1,m1);   // -(2+i)
    // isogeny constants via Velu from kernel x0=-6+6i, orientation (x/9,-y/27)
    Fp f6={{6,0,0,0,0,0}}, f9={{9,0,0,0,0,0}}, f27={{27,0,0,0,0,0}};
    Fp m6,m9,m27; fp_to_mont(m6,f6); fp_to_mont(m9,f9); fp_to_mont(m27,f27);
    Fp2 x0; fp_neg(x0.c0,m6); x0.c1=m6;
    Fp2 x0sq,x0cu,gx0,t1,uu,inv9,inv27,tmp;
    fp2_sqr(x0sq,x0); fp2_mul(x0cu,x0sq,x0);
    Fp2 ax0; fp2_mul(ax0,SSWU_A,x0);
    fp2_add(gx0,x0cu,ax0); fp2_add(gx0,gx0,SSWU_B);
    Fp2 th; fp2_add(th,x0sq,x0sq); fp2_add(th,th,x0sq); fp2_add(th,th,SSWU_A); // 3x0^2+A
    fp2_add(t1,th,th);
    fp2_add(uu,gx0,gx0); fp2_add(uu,uu,uu);        // 4 y0^2
    Fp i9,i27; fp_inv(i9,m9); fp_inv(i27,m27);
    // x_num/9
    Fp2 t1x0; fp2_mul(t1x0,t1,x0);
    fp2_sub(tmp,uu,t1x0); fp2_mul_fp(ISO_XN[0],tmp,i9);
    fp2_add(tmp,x0sq,t1);  fp2_mul_fp(ISO_XN[1],tmp,i9);
    fp2_add(tmp,x0,x0); fp2_neg(tmp,tmp); fp2_mul_fp(ISO_XN[2],tmp,i9);
    ISO_XN[3].c0=i9; ISO_XN[3].c1=FP_ZERO;
    // x_den: x^2 - 2x0 x + x0^2 (monic)
    ISO_XD[0]=x0sq;
    fp2_add(tmp,x0,x0); fp2_neg(ISO_XD[1],tmp);
    // y_num: -[(x-x0)^3 - t1(x-x0) - 2u]/27
    Fp2 u2_; fp2_add(u2_,uu,uu);                    // 2u
    fp2_neg(tmp,x0cu); fp2_add(tmp,tmp,t1x0); fp2_sub(tmp,tmp,u2_);
    fp2_mul_fp(tmp,tmp,i27); fp2_neg(ISO_YN[0],tmp);
    Fp2 thr; fp2_add(thr,x0sq,x0sq); fp2_add(thr,thr,x0sq);   // 3x0^2
    fp2_sub(tmp,thr,t1); fp2_mul_fp(tmp,tmp,i27); fp2_neg(ISO_YN[1],tmp);
    fp2_add(tmp,x0,x0); fp2_add(tmp,tmp,x0); fp2_neg(tmp,tmp);
    fp2_mul_fp(tmp,tmp,i27); fp2_neg(ISO_YN[2],tmp);
    ISO_YN[3].c0=FP_ZERO; fp_neg(ISO_YN[3].c0,i27); ISO_YN[3].c1=FP_ZERO;
    // y_den: (x-x0)^3 monic: x^3 - 3x0 x^2 + 3x0^2 x - x0^3
    fp2_neg(ISO_YD[0],x0cu);
    ISO_YD[1]=thr;
    fp2_add(tmp,x0,x0); fp2_add(tmp,tmp,x0); fp2_neg(ISO_YD[2],tmp);
    // psi constants: PSI_CX = gamma^4 * xi * conj(xi)^-1, PSI_CY = gamma^3 * ...
    {
        Fp2 cxi,cxi_inv,k;
        fp2_conj(cxi,xi); fp2_inv(cxi_inv,cxi);
        fp2_mul(k,xi,cxi_inv);
        fp2_mul(PSI_CX,FROB_G[4],k);
        fp2_mul(PSI_CY,FROB_G[3],k);
    }
    INITED=true;
    // Runtime-verify the fast G2 paths against the slow ones before
    // enabling them (misremembered endomorphism identities fail safe).
    {
        // on-curve NON-subgroup points: solve y^2 = x^3 + 4xi for small x
        G2 bad[2]; int nbad=0;
        for(u64 xi_c0=1; nbad<2 && xi_c0<50; xi_c0++){
            Fp c={{xi_c0,0,0,0,0,0}};
            Fp2 x; fp_to_mont(x.c0,c); x.c1=FP_ZERO;
            Fp2 rhs,y; fp2_sqr(rhs,x); fp2_mul(rhs,rhs,x); fp2_add(rhs,rhs,B2_M);
            if(!fp2_sqrt(y,rhs)) continue;
            G2 q; q.x=x; q.y=y; q.z=FP2_ONE;
            if(g2_in_subgroup_slow(q)) continue;
            bad[nbad++]=q;
        }
        G2 goods[2]; u8 k1[2]={0x12,0x34};
        g2_mul(goods[0],G2_GEN,k1,2);
        u8 k2[3]={0x05,0x07,0x09};
        g2_mul(goods[1],G2_GEN,k2,3);
        bool ok=true;
        for(int i=0;i<2&&ok;i++){
            G2 psi_q,uq;
            g2_psi(psi_q,goods[i]);
            g2_mul_u64(uq,goods[i],U_ABS); g2_neg(uq,uq);
            ok=g2_eq(psi_q,uq);
        }
        for(int i=0;i<nbad&&ok;i++){
            G2 psi_q,uq;
            g2_psi(psi_q,bad[i]);
            g2_mul_u64(uq,bad[i],U_ABS); g2_neg(uq,uq);
            ok=!g2_eq(psi_q,uq);     // must REJECT non-subgroup points
        }
        USE_FAST_SUBGROUP=ok&&nbad==2;
        bool cok=nbad==2;
        for(int i=0;i<nbad&&cok;i++){
            G2 slow,fast;
            g2_clear_cofactor_slow(slow,bad[i]);
            USE_FAST_COFACTOR=true; g2_clear_cofactor(fast,bad[i]);
            USE_FAST_COFACTOR=false;
            cok=g2_eq(slow,fast);
        }
        USE_FAST_COFACTOR=cok;
    }
    u128 u2=(u128)U_ABS*U_ABS;
    for(int i=0;i<16;i++) U2_BYTES_BE[i]=(u8)(u2>>(120-8*i));
    fp_to_mont(G1_BETA,G1_BETA_PLAIN);
}

// ---------------------------------------------------------------------------
// extern "C" API (ctypes surface; all byte args big-endian / zcash format)
// ---------------------------------------------------------------------------
extern "C" {

int bls_selftest(){
    ensure_init();
    if(!g1_on_curve(G1_GEN)||!g2_on_curve(G2_GEN)) return 1;
    if(!g1_in_subgroup(G1_GEN)||!g2_in_subgroup(G2_GEN)) return 2;
    // bilinearity: e(2G1, 3G2) * e(-6 G1, G2) == 1
    u8 two[1]={2}, three[1]={3}, six[1]={6};
    G1 p2,p6n; G2 q3;
    g1_mul(p2,G1_GEN,two,1); g2_mul(q3,G2_GEN,three,1);
    g1_mul(p6n,G1_GEN,six,1); g1_neg(p6n,p6n);
    std::vector<PairAff> ps(2);
    Fp ax,ay; Fp2 bx,by;
    g1_to_affine(ax,ay,p2); g2_to_affine(bx,by,q3);
    ps[0]={ax,ay,bx,by,bx,by,false};
    g1_to_affine(ax,ay,p6n); g2_to_affine(bx,by,G2_GEN);
    ps[1]={ax,ay,bx,by,bx,by,false};
    Fp12 f; multi_miller(f,ps);
    if(!pairing_product_is_one(f)) return 3;
    // non-degeneracy: e(G1,G2) != 1
    std::vector<PairAff> one(1);
    g1_to_affine(ax,ay,G1_GEN); g2_to_affine(bx,by,G2_GEN);
    one[0]={ax,ay,bx,by,bx,by,false};
    multi_miller(f,one);
    if(pairing_product_is_one(f)) return 4;
    return 0;
}

int bls_sk_to_pk(const u8*sk32,u8*out48){
    ensure_init();
    G1 p; g1_mul(p,G1_GEN,sk32,32);
    g1_compress(out48,p);
    return 0;
}

int bls_hash_to_g2(const u8*msg,size_t msglen,const u8*dst,size_t dstlen,u8*out96){
    ensure_init();
    G2 h; hash_to_g2(h,msg,msglen,dst,dstlen);
    g2_compress(out96,h);
    return 0;
}

int bls_sign(const u8*sk32,const u8*msg,size_t msglen,
             const u8*dst,size_t dstlen,u8*out96){
    ensure_init();
    G2 h,s; hash_to_g2(h,msg,msglen,dst,dstlen);
    g2_mul(s,h,sk32,32);
    g2_compress(out96,s);
    return 0;
}

// one signature set: sig(96) over msg by n_pks aggregated pubkeys (48 each).
// Layout mirrors crypto/bls12_381/sig.py verify_signature_sets_rlc.
// sets: n entries; pks concatenated, pk_counts[i] pubkeys for set i;
// rands: one u64 blinding scalar per set (caller supplies; 1 for single).
int bls_verify_signature_sets(size_t n,const u8*sigs,const u8*pks,
                              const uint32_t*pk_counts,
                              const u8*msgs,const uint32_t*msg_lens,
                              const u8*dst,size_t dstlen,
                              const u64*rands){
    ensure_init();
    if(n==0) return 0;
    // per-set offsets
    std::vector<size_t> pk_off(n), msg_off(n);
    size_t po=0,mo=0;
    for(size_t i=0;i<n;i++){ pk_off[i]=po; po+=48ul*pk_counts[i];
                             msg_off[i]=mo; mo+=msg_lens[i]; }
    std::vector<PairAff> ps(n+1);
    std::vector<G2> rsigs(n);
    std::vector<int> okv(n,0);
    // parallel per-set prep: decompress, subgroup check, hash, blind
    auto prep=[&](size_t lo,size_t hi){
        for(size_t i=lo;i<hi;i++){
            G2 sig;
            if(!g2_decompress(sig,sigs+96*i)) continue;
            if(g2_is_inf(sig)) continue;
            if(!g2_on_curve(sig)||!g2_in_subgroup(sig)) continue;
            if(pk_counts[i]==0) continue;
            G1 pk={FP_ONE_M,FP_ONE_M,FP_ZERO};
            bool pk_ok=true;
            const u8*pc=pks+pk_off[i];
            for(uint32_t j=0;j<pk_counts[i];j++,pc+=48){
                G1 one;
                if(!g1_decompress(one,pc)){ pk_ok=false; break; }
                g1_add(pk,pk,one);
            }
            if(!pk_ok||g1_is_inf(pk)) continue;
            u8 rb[8]; for(int b=0;b<8;b++) rb[b]=(u8)(rands[i]>>(56-8*b));
            G1 rpk; g1_mul(rpk,pk,rb,8);
            g2_mul(rsigs[i],sig,rb,8);
            G2 h; hash_to_g2(h,msgs+msg_off[i],msg_lens[i],dst,dstlen);
            PairAff&pa=ps[i]; Fp2 qx,qy;
            g1_to_affine(pa.px,pa.py,rpk);
            g2_to_affine(qx,qy,h);
            pa.qx=qx; pa.qy=qy; pa.tx=qx; pa.ty=qy; pa.inf=false;
            okv[i]=1;
        }
    };
    unsigned nt=std::thread::hardware_concurrency();
    if(nt<1) nt=1;
    if(nt>8) nt=8;
    if(n<4||nt==1){ prep(0,n); }
    else {
        std::vector<std::thread> th;
        size_t chunk=(n+nt-1)/nt;
        for(unsigned t=0;t<nt;t++){
            size_t lo=t*chunk, hi=lo+chunk<n?lo+chunk:n;
            if(lo>=hi) break;
            th.emplace_back(prep,lo,hi);
        }
        for(auto&x:th) x.join();
    }
    for(size_t i=0;i<n;i++) if(!okv[i]) return 0;
    G2 agg_sig={FP2_ONE,FP2_ONE,FP2_ZERO};
    for(size_t i=0;i<n;i++) g2_add(agg_sig,agg_sig,rsigs[i]);
    G1 negg; g1_neg(negg,G1_GEN);
    PairAff&last=ps[n]; Fp2 ax,ay;
    g1_to_affine(last.px,last.py,negg);
    g2_to_affine(ax,ay,agg_sig);
    last.qx=ax; last.qy=ay; last.tx=ax; last.ty=ay; last.inf=false;
    Fp12 f; multi_miller(f,ps);
    return pairing_product_is_one(f)?1:0;
}

// pk_i signed msg_i; one aggregate signature (oracle aggregate_verify)
int bls_aggregate_verify(size_t n,const u8*pks,const u8*msgs,
                         const uint32_t*msg_lens,const u8*sig96,
                         const u8*dst,size_t dstlen){
    ensure_init();
    if(n==0) return 0;
    G2 sig;
    if(!g2_decompress(sig,sig96)) return 0;
    if(g2_is_inf(sig)) return 0;
    if(!g2_on_curve(sig)||!g2_in_subgroup(sig)) return 0;
    std::vector<PairAff> ps(n+1);
    const u8*mc=msgs;
    for(size_t i=0;i<n;i++){
        G1 pk;
        if(!g1_decompress(pk,pks+48*i)) return 0;
        if(g1_is_inf(pk)) return 0;
        G2 h; hash_to_g2(h,mc,msg_lens[i],dst,dstlen);
        mc+=msg_lens[i];
        PairAff&pa=ps[i]; Fp2 qx,qy;
        g1_to_affine(pa.px,pa.py,pk);
        g2_to_affine(qx,qy,h);
        pa.qx=qx; pa.qy=qy; pa.tx=qx; pa.ty=qy; pa.inf=false;
    }
    G1 negg; g1_neg(negg,G1_GEN);
    PairAff&last=ps[n]; Fp2 ax,ay;
    g1_to_affine(last.px,last.py,negg);
    g2_to_affine(ax,ay,sig);
    last.qx=ax; last.qy=ay; last.tx=ax; last.ty=ay; last.inf=false;
    Fp12 f; multi_miller(f,ps);
    return pairing_product_is_one(f)?1:0;
}

int bls_fast_paths(){
    ensure_init();
    return (USE_FAST_SUBGROUP?1:0)|(USE_FAST_COFACTOR?2:0);
}

int bls_aggregate_sigs(size_t n,const u8*sigs,u8*out96){
    ensure_init();
    G2 acc={FP2_ONE,FP2_ONE,FP2_ZERO};
    for(size_t i=0;i<n;i++){
        G2 s; if(!g2_decompress(s,sigs+96*i)) return 1;
        g2_add(acc,acc,s);
    }
    g2_compress(out96,acc);
    return 0;
}
int bls_aggregate_pks(size_t n,const u8*pks,u8*out48){
    ensure_init();
    G1 acc={FP_ONE_M,FP_ONE_M,FP_ZERO};
    for(size_t i=0;i<n;i++){
        G1 p; if(!g1_decompress(p,pks+48*i)) return 1;
        g1_add(acc,acc,p);
    }
    g1_compress(out48,acc);
    return 0;
}
int bls_validate_pubkey(const u8*pk48){
    ensure_init();
    G1 p;
    if(!g1_decompress(p,pk48)) return 0;
    if(g1_is_inf(p)) return 0;
    return g1_in_subgroup(p)?1:0;
}
// bulk pubkey load (the tpu backend's device pubkey table): n compressed
// keys -> affine x || y (48 bytes big-endian each) in out96, and ok[i] = 1
// where key i passes KeyValidate (decodes, not the identity, in G1);
// rows of failed keys are zero.  Split over `threads` threads.
int bls_g1_decompress_batch(size_t n,const u8*pks,u8*out96,u8*ok,
                            size_t threads){
    ensure_init();
    auto work=[&](size_t lo,size_t hi){
        for(size_t i=lo;i<hi;i++){
            G1 p; u8*o=out96+96*i;
            ok[i]=0; memset(o,0,96);
            if(!g1_decompress(p,pks+48*i)||g1_is_inf(p)
               ||!g1_in_subgroup(p))
                continue;
            Fp t;                          // decompression leaves z = 1
            fp_from_mont(t,p.x); fp_to_be(o,t);
            fp_from_mont(t,p.y); fp_to_be(o+48,t);
            ok[i]=1;
        }
    };
    if(threads<1) threads=1;
    size_t chunk=(n+threads-1)/threads;
    if(threads==1||chunk<64){ work(0,n); return 0; }
    std::vector<std::thread> th;
    for(size_t lo=0;lo<n;lo+=chunk)
        th.emplace_back(work,lo,lo+chunk<n?lo+chunk:n);
    for(auto&x:th) x.join();
    return 0;
}
// cross-check helpers: expose uncompressed affine coords of hash_to_g2
int bls_hash_to_g2_affine(const u8*msg,size_t msglen,const u8*dst,size_t dstlen,
                          u8*out192){
    ensure_init();
    G2 h; hash_to_g2(h,msg,msglen,dst,dstlen);
    Fp2 x,y; g2_to_affine(x,y,h);
    Fp t;
    fp_from_mont(t,x.c0); fp_to_be(out192,t);
    fp_from_mont(t,x.c1); fp_to_be(out192+48,t);
    fp_from_mont(t,y.c0); fp_to_be(out192+96,t);
    fp_from_mont(t,y.c1); fp_to_be(out192+144,t);
    return 0;
}

// --- KZG host support (the c-kzg-equivalent half of SURVEY.md §2.6) --------
// Generic G1 multi-scalar multiplication and multi-pairing check; the KZG
// layer (crypto/kzg.py) reduces commit/verify to exactly these two calls.

// out48 = sum_i scalars[i] * points[i].  scalars: 32-byte big-endian each;
// points: compressed 48-byte each (infinity allowed).  rc 0 ok, 1 decode.
int kzg_g1_msm(size_t n,const u8*scalars,const u8*points,u8*out48){
    ensure_init();
    G1 acc={FP_ONE_M,FP_ONE_M,FP_ZERO};
    for(size_t i=0;i<n;i++){
        G1 p; if(!g1_decompress(p,points+48*i)) return 1;
        if(g1_is_inf(p)) continue;
        // skip zero scalars (common: sparse polynomial coefficients)
        u64 nz=0; for(int j=0;j<32;j++) nz|=scalars[32*i+j];
        if(!nz) continue;
        G1 t; g1_mul(t,p,scalars+32*i,32);
        g1_add(acc,acc,t);
    }
    g1_compress(out48,acc);
    return 0;
}

// prod_i e(P_i, Q_i) == 1 ?  P: compressed 48B each (subgroup-checked);
// Q: compressed 96B each (subgroup-checked).  rc 1 yes, 0 no, -1 decode
// or subgroup failure.
int kzg_pairing_check(size_t n,const u8*g1s,const u8*g2s){
    ensure_init();
    std::vector<PairAff> ps;
    for(size_t i=0;i<n;i++){
        G1 p; G2 q;
        if(!g1_decompress(p,g1s+48*i)) return -1;
        if(!g2_decompress(q,g2s+96*i)) return -1;
        if(!g1_is_inf(p)&&!g1_in_subgroup(p)) return -1;
        if(!g2_is_inf(q)&&!g2_in_subgroup(q)) return -1;
        if(g1_is_inf(p)||g2_is_inf(q)) continue;   // factor contributes 1
        Fp ax,ay; Fp2 bx,by;
        g1_to_affine(ax,ay,p); g2_to_affine(bx,by,q);
        PairAff pr; pr.px=ax; pr.py=ay; pr.qx=bx; pr.qy=by;
        pr.tx=bx; pr.ty=by; pr.inf=false;
        ps.push_back(pr);
    }
    if(ps.empty()) return 1;
    Fp12 f; multi_miller(f,ps);
    return pairing_product_is_one(f)?1:0;
}

// single G1 scalar mul (setup generation helper): out = k * point.
int kzg_g1_mul(const u8*scalar32,const u8*point48,u8*out48){
    ensure_init();
    G1 p; if(!g1_decompress(p,point48)) return 1;
    G1 t; g1_mul(t,p,scalar32,32);
    g1_compress(out48,t);
    return 0;
}

} // extern "C"
