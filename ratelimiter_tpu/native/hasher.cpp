// Bulk 64-bit string hashing — the host-ingest hot path.
//
// The reference ships raw string keys to Redis and lets the store hash them
// (SURVEY.md §2.4.8); here keys are reduced to u64 on the host at ingest
// (SURVEY.md §7.4 hard part #4) and this translation unit is the native
// fast path for doing that in bulk. Two entry points:
//
// * hash_keylist (CPython module function): iterates a Python list of str
//   directly — PyUnicode_AsUTF8AndSize is zero-copy for ASCII and cached
//   per object — so there is NO Python-level packing step at all. This is
//   what ops/hashing.hash_strings_u64 uses.
// * rl_bulk_hash_u64 (plain C ABI, ctypes): hashes a pre-packed
//   buffer+offsets+lengths batch; kept for the NumPy-twin cross-checks and
//   for callers that already hold packed bytes.
//
// The algorithm is a word-at-a-time multiply-rotate construction in the
// xxHash/Murmur family (8-byte little-endian lanes, one round per lane,
// splitmix64 finalizer). It is defined by THIS file plus its bit-identical
// NumPy twin (ratelimiter_tpu/native/fallback.py) and a scalar Python
// reference (tests/test_hashing.py); the three are cross-checked in tests.
// Little-endian hosts only (x86-64 / aarch64 — every TPU host qualifies).
//
// Build: make native  (g++ -O3 -shared -fPIC -I$PYTHON_INCLUDE hasher.cpp)
//        — or automatically on first import (native/__init__.py).

#include <Python.h>

#include <cstdint>
#include <cstring>

namespace {

constexpr uint64_t P1 = 0x9E3779B185EBCA87ULL;  // golden-ratio primes
constexpr uint64_t P2 = 0xC2B2AE3D27D4EB4FULL;
constexpr uint64_t P3 = 0x165667B19E3779F9ULL;

inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

// splitmix64 finalizer — same mix as ops/hashing.splitmix64, so integer-id
// and string-key hashes share avalanche quality.
inline uint64_t fmix64(uint64_t x) {
  x ^= x >> 30; x *= 0xBF58476D1CE4E5B9ULL;
  x ^= x >> 27; x *= 0x94D049BB133111EBULL;
  x ^= x >> 31;
  return x;
}

inline uint64_t round64(uint64_t h, uint64_t lane) {
  return rotl64(h ^ (lane * P1), 27) * P2 + P3;
}

inline uint64_t hash_one(const uint8_t* p, int64_t len, uint64_t seed) {
  uint64_t h = seed ^ (static_cast<uint64_t>(len) * P1);
  const int64_t nw = len >> 3;
  for (int64_t w = 0; w < nw; ++w) {
    uint64_t lane;
    std::memcpy(&lane, p + 8 * w, 8);
    h = round64(h, lane);
  }
  const int64_t rem = len & 7;
  if (rem) {
    uint64_t lane = 0;
    std::memcpy(&lane, p + 8 * nw, static_cast<size_t>(rem));
    h = round64(h, lane);
  }
  return fmix64(h);
}

}  // namespace

extern "C" {

// Hash n byte strings packed back-to-back in buf. offsets[i]/lengths[i]
// locate key i; out receives the 64-bit hashes. Single pass, no allocation.
void rl_bulk_hash_u64(const uint8_t* buf, const int64_t* offsets,
                      const int64_t* lengths, uint64_t seed,
                      uint64_t* out, int64_t n) {
  for (int64_t i = 0; i < n; ++i) {
    out[i] = hash_one(buf + offsets[i], lengths[i], seed);
  }
}

// ABI version so the Python loader can reject a stale .so after the
// algorithm changes.
int64_t rl_hasher_abi_version() { return 2; }

// SHA-256 of the sources this object was built from (native/build.py
// passes it in and reads the marker back out of the file's bytes to
// decide whether the binary on disk belongs to this checkout).
#ifndef RL_SRC_HASH
#define RL_SRC_HASH "unhashed"
#endif
const char* rl_hasher_src_hash() { return "RL_SRC_HASH:" RL_SRC_HASH; }

}  // extern "C"

// ------------------------------------------------------------------ module

// hash_keylist(keys: list[str], seed: int, out_addr: int) -> None
// Writes hashes into the uint64 buffer at out_addr (len(keys) elements) —
// the caller (native/__init__.py) owns a numpy array and passes
// arr.ctypes.data, which keeps numpy headers out of the build.
static PyObject* hash_keylist(PyObject*, PyObject* args) {
  PyObject* list;
  unsigned long long seed;
  unsigned long long out_addr;
  if (!PyArg_ParseTuple(args, "O!KK", &PyList_Type, &list, &seed, &out_addr)) {
    return nullptr;
  }
  uint64_t* out = reinterpret_cast<uint64_t*>(out_addr);
  const Py_ssize_t n = PyList_GET_SIZE(list);
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* item = PyList_GET_ITEM(list, i);  // borrowed
    Py_ssize_t len;
    const char* data = PyUnicode_AsUTF8AndSize(item, &len);
    if (data == nullptr) {
      return nullptr;  // not a str (or encode failure) — TypeError raised
    }
    out[i] = hash_one(reinterpret_cast<const uint8_t*>(data),
                      static_cast<int64_t>(len),
                      static_cast<uint64_t>(seed));
  }
  Py_RETURN_NONE;
}

static PyMethodDef kMethods[] = {
    {"hash_keylist", hash_keylist, METH_VARARGS,
     "Hash a list of str into the uint64 buffer at out_addr."},
    {nullptr, nullptr, 0, nullptr},
};

static struct PyModuleDef kModule = {
    PyModuleDef_HEAD_INIT, "_hasher",
    "Native bulk string hasher (see hasher.cpp).", -1, kMethods,
    nullptr, nullptr, nullptr, nullptr,
};

PyMODINIT_FUNC PyInit__hasher(void) { return PyModule_Create(&kModule); }
