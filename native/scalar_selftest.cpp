// Sanitizer self-test for the msm epilogue's field and group arithmetic in
// scalar_ops.cpp (the storage engine's twin is engine_selftest.cpp). Known
// answers come from the plain-integer reference; the epilogue itself is
// driven with window sums whose answer is known by construction, on limbs
// as loose and as negative as int32 holds. Build+run via native/sanitize.sh.

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>

extern "C" {
void fe_test(int64_t op, const uint8_t* a, const uint8_t* b, uint8_t* out);
void pt_test(int64_t op, const uint8_t* p, const uint8_t* q, uint8_t* out);
void fe_loose13_test(const int32_t* limbs, uint8_t* out);
int msm_epilogue_native(const int32_t* va, const int32_t* vr, int64_t wr, const uint8_t* sum_s);
void scalar_mulmod(int64_t m, const uint8_t* a_rows, const uint8_t* b_rows, uint8_t* out_rows);
}

// Known answers, from narwhal_tpu/tpu/ed25519_ref.py (32-byte little-endian
// hex; a point is X, Y, Z, T as the reference's formulas leave them mod p).
static const char* FE_A = "efcdab9078563412efcdab9078563412efcdab9078563412efcdab9078563412";
static const char* FE_C = "e8ffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f";  // p - 5
static const char* FE_A_PLUS_C = "eacdab9078563412efcdab9078563412efcdab9078563412efcdab9078563412";
static const char* FE_A_MINUS_C = "f4cdab9078563412efcdab9078563412efcdab9078563412efcdab9078563412";
static const char* FE_A_TIMES_C = "42faa42ca54ffaa454faa42ca54ffaa454faa42ca54ffaa454faa42ca54ffa24";
static const char* PT_B[4] = {"1ad5258f602d56c9b2a7259560c72c695cdcd6fd31e2a4c0fe536ecdd3366921", "5866666666666666666666666666666666666666666666666666666666666666", "0100000000000000000000000000000000000000000000000000000000000000", "a3ddb7a5b38ade6df5525177809ff0207de3ab648e4eea6665768bd70f5f8767"};
static const char* PT_NEG_B[4] = {"d32ada709fd2a9364d58da6a9f38d396a3232902ce1d5b3f01ac91322cc9965e", "5866666666666666666666666666666666666666666666666666666666666666", "0100000000000000000000000000000000000000000000000000000000000000", "4a22485a4c7521920aadae887f600fdf821c549b71b115999a897428f0a07818"};
static const char* PT_B_PLUS_B[4] = {"7ed63f9fcfe6151592763a1688f844ded91b3c21e1d17657d46a0f9691886f3b", "7e004a879a4cd3b68d3707516756e1d627b8da140ff421599230db4cce9e6d33", "08861cf60b5b2d72ba98b531ff7bb250b475f61217b89cfda20ea2521aeae459", "89d9ca92e3c732c59b133d4800957472c1075eddfea66efcaa8d292dda086e1f"};
static const char* PT_DOUBLE_B[4] = {"570a30184c86ba7a5b6271faddc16e8809f9b0b7874b22ea4a257c9adb1d2431", "d77f2d5ed92c4b921c32be2b66aa470af651c93afc82b769db33c96c4c982433", "6bde78023da97463d19992330061d3eb9262423bfad19840577c576b79c58669", "99494d1b074eb30e19bbf0edbfda62a30f7ea8484056e440959cb574c97d2418"};
static const char* PT_2B_PLUS_B[4] = {"e0f80dc9cf564159b356afb6cc2c06db49e4351db005f2f871ea5fbe81bd797c", "a75c73507fc074d3c9a95316f21b6d581a1d36e04fc3cd601499a8ebc6d8eb1e", "fe9ea6fbd959bcf97532529d44c0f38c952c40a259c1e0942be4ac8350f40101", "bb04a08ae45601273d5f86c0286ffa1f5ba19f949f870dbf1df85d66afd71712"};
static const char* PT_TABLE_3[4] = {"68f2f80dc9cf564159b356afb6cc2c06db49e4351db005f2f871ea5fbe81bd79", "87ab5c73507fc074d3c9a95316f21b6d581a1d36e04fc3cd601499a8ebc6d86b", "26fe9ea6fbd959bcf97532529d44c0f38c952c40a259c1e0942be4ac8350f401", "acbd04a08ae45601273d5f86c0286ffa1f5ba19f949f870dbf1df85d66afd717"};  // ((identity + B) + B) + B
static const char* FE_ALL_INT32_MIN = "edb37f76ffeffffdbffff7fffedffffb7fffeffffdbffff7fffedffffb7fff6f";  // twenty limbs of -2^31
static const char* FE_ALL_INT32_MAX = "ff2b8085800ff0013ec007f8001fe0037c800ff0013ec007f8001fe0037c800f";  // twenty limbs of 2^31 - 1

static int failures = 0;

static void check(bool ok, const char* what) {
  if (!ok) {
    std::fprintf(stderr, "FAILED: %s\n", what);
    ++failures;
  }
}

static void unhex(const char* hex, uint8_t out[32]) {
  for (int i = 0; i < 32; ++i) {
    unsigned v = 0;
    std::sscanf(hex + 2 * i, "%2x", &v);
    out[i] = (uint8_t)v;
  }
}

static bool is_hex(const uint8_t got[32], const char* hex) {
  uint8_t want[32];
  unhex(hex, want);
  return std::memcmp(got, want, 32) == 0;
}

static void point(const char* const hex[4], uint8_t out[128]) {
  for (int c = 0; c < 4; ++c) unhex(hex[c], out + 32 * c);
}

static bool is_point(const uint8_t got[128], const char* const hex[4]) {
  uint8_t want[128];
  point(hex, want);
  return std::memcmp(got, want, 128) == 0;
}

// 32 canonical bytes -> twenty tight radix-2^13 limbs, `stride` apart.
static void limbs13(const uint8_t in[32], int32_t* out, int64_t stride) {
  for (int i = 0; i < 20; ++i) {
    int bit = 13 * i;
    uint32_t v = 0;
    for (int k = 0; k < 3 && bit / 8 + k < 32; ++k) v |= (uint32_t)in[bit / 8 + k] << (8 * k);
    out[i * stride] = (int32_t)((v >> (bit % 8)) & 8191);
  }
}

// The same value on other limbs: `c` units of limb i+1 carried down into
// limb i, signs alternating, for every i; then `top` multiples of p on the
// ends. `c` and `top` of one sign keep limb 0 inside int32.
static void loosen(int32_t* limbs, int64_t stride, int32_t c, int32_t top) {
  int64_t wide[20];
  for (int i = 0; i < 20; ++i) wide[i] = limbs[i * stride];
  for (int i = 0; i < 19; ++i) {
    int64_t k = (i % 2) ? -c : c;
    wide[i] += 8192 * k;
    wide[i + 1] -= k;
  }
  wide[19] += 256 * (int64_t)top;  // 2^255 = 256 * 2^(13 * 19)
  wide[0] -= 19 * (int64_t)top;
  for (int i = 0; i < 20; ++i) {
    if (wide[i] < INT32_MIN || wide[i] > INT32_MAX) std::abort();  // the test's own fault
    limbs[i * stride] = (int32_t)wide[i];
  }
}

struct sums {
  int32_t va[4 * 20 * 64];
  int32_t vr[4 * 20 * 64];
  int64_t wr;
};

static void put(int32_t* v, int64_t lanes, int64_t lane, const char* const hex[4], int32_t c, int32_t top) {
  uint8_t bytes[128];
  point(hex, bytes);
  for (int k = 0; k < 4; ++k) {
    int32_t* limbs = v + (k * 20) * lanes + lane;
    limbs13(bytes + 32 * k, limbs, lanes);
    loosen(limbs, lanes, c, top);
  }
}

static const char* ZERO = "0000000000000000000000000000000000000000000000000000000000000000";
static const char* ONE = "0100000000000000000000000000000000000000000000000000000000000000";
static const char* const PT_ID[4] = {ZERO, ONE, ONE, ZERO};

static void all_identity(sums& s, int64_t wr, int32_t c, int32_t top) {
  s.wr = wr;
  for (int64_t w = 0; w < 64; ++w) put(s.va, 64, w, PT_ID, c, top);
  for (int64_t w = 0; w < wr; ++w) put(s.vr, wr, w, PT_ID, c, top);
}

static int run(const sums& s, uint64_t sum_s) {
  uint8_t le[32] = {0};
  std::memcpy(le, &sum_s, 8);
  return msm_epilogue_native(s.va, s.vr, s.wr, le);
}

int main() {
  uint8_t a[32], c[32], out[128], p[128], q[128];

  unhex(FE_A, a);
  unhex(FE_C, c);
  fe_test(0, a, c, out);
  check(is_hex(out, FE_A_PLUS_C), "a + c");
  fe_test(1, a, c, out);
  check(is_hex(out, FE_A_MINUS_C), "a - c");
  fe_test(2, a, c, out);
  check(is_hex(out, FE_A_TIMES_C), "a * c");
  std::memset(p, 0xff, 32);  // 2^256 - 1, taken mod p: 37
  std::memset(q, 0, 32);
  fe_test(0, p, q, out);
  std::memset(q, 0, 32);
  q[0] = 37;
  check(std::memcmp(out, q, 32) == 0, "bytes over p reduce");

  point(PT_B, p);
  pt_test(0, p, p, out);
  check(is_point(out, PT_B_PLUS_B), "B + B");
  pt_test(1, p, p, out);
  check(is_point(out, PT_DOUBLE_B), "2B");
  point(PT_B_PLUS_B, q);
  pt_test(0, q, p, out);
  check(is_point(out, PT_2B_PLUS_B), "2B + B");
  std::memset(q, 0, 128);
  q[0] = 3;
  pt_test(2, p, q, out);
  check(is_point(out, PT_TABLE_3), "[3]B from the table");

  int32_t limbs[20];
  for (int i = 0; i < 20; ++i) limbs[i] = INT32_MIN;
  fe_loose13_test(limbs, out);
  check(is_hex(out, FE_ALL_INT32_MIN), "twenty limbs of INT32_MIN");
  for (int i = 0; i < 20; ++i) limbs[i] = INT32_MAX;
  fe_loose13_test(limbs, out);
  check(is_hex(out, FE_ALL_INT32_MAX), "twenty limbs of INT32_MAX");

  // The epilogue: [8]([sum_s]B + sum_w 16^(63-w)(V_a[w] + V_r[w - (64 - wr)])).
  static sums s;
  // Tight limbs, then loose ones of either sign, then up to the edge of
  // int32: 8192 c + c + 8191 and 256 top + c + 8191 stay just under 2^31.
  const int32_t shapes[3][2] = {{0, 0}, {5, 3}, {262100, 8387000}};
  for (const auto& shape : shapes) {
    int32_t cc = shape[0], top = shape[1];
    all_identity(s, 32, cc, top);
    check(run(s, 0) == 1, "nothing at all is the identity");
    check(run(s, 1) == 0, "[1]B alone is not");
    check(run(s, 8) == 0, "[8]B alone is not: the cofactor clears torsion only");
    put(s.va, 64, 63, PT_NEG_B, cc, top);
    check(run(s, 1) == 1, "[1]B - B in the last window");
    check(run(s, 2) == 0, "[2]B - B");
    put(s.va, 64, 62, PT_NEG_B, -cc, -top);
    check(run(s, 17) == 1, "[17]B - 16 B - B across two windows");
    put(s.vr, 32, 31, PT_B, cc, top);
    check(run(s, 16) == 1, "V_r's last window lands on V_a's last");
    put(s.vr, 32, 0, PT_NEG_B, -cc, -top);
    check(run(s, 16) == 0, "V_r's first window is window 32");
    all_identity(s, 64, cc, top);
    put(s.vr, 64, 0, PT_NEG_B, cc, top);
    uint8_t le[32] = {0};
    le[31] = 0x10;  // 16^63: the first window's weight, as a scalar
    check(msm_epilogue_native(s.va, s.vr, 64, le) == 1, "a V_r as wide as V_a: its window 0 is window 0");
  }
  check(msm_epilogue_native(s.va, s.vr, 0, a) == -1, "no V_r windows is refused");
  check(msm_epilogue_native(s.va, s.vr, 65, a) == -1, "more than 64 is refused");

  // (L - 1) * (L - 1) = 1 mod L: the scalar half shares the build.
  uint8_t lm1[32], one[32] = {1};
  unhex("ecd3f55c1a631258d69cf7a2def9de1400000000000000000000000000000010", lm1);
  scalar_mulmod(1, lm1, lm1, out);
  check(std::memcmp(out, one, 32) == 0, "(L - 1)^2 mod L");

  if (failures) return 1;
  std::puts("scalar self-test ok");
  return 0;
}
