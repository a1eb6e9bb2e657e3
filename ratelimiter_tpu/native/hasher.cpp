// The host's two per-row passes: bulk 64-bit string hashing at ingest, and
// the rebuild of a dispatch's reply columns at resolve (unpack_columns,
// below the hasher).
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

// ------------------------------------------------- the resolve's columns
//
// A device step leaves ONE packed int32 buffer a shard (ops/sketch_kernels
// .pack_rows: `rows` runs of `pl` words, then the shard's tail words);
// resolve rebuilds BatchResult's four columns from it. unpack_columns is
// that rebuild as one pass that holds the GIL from its first line to its
// last — the NumPy twins (sketch_kernels.unpack_window, bucket_kernels
// .unpack_bucket, dense_kernels.unpack_dense) are 4 to 14 array calls, each
// of which lets go of the interpreter above 500 elements and waits to get
// it back from the door's other threads. Bit for bit the twins' columns:
// int32 -> int64 sign-extended, (double)int64 / 1e6 in IEEE float64, no
// -ffast-math.

namespace {

enum Format { kWindow = 0, kBucket = 1, kDense = 2 };

// sketch_kernels.join_words: the high word sign-extended, the low not.
inline int64_t join_words(int32_t low, int32_t high) {
  return static_cast<int64_t>(
      (static_cast<uint64_t>(static_cast<int64_t>(high)) << 32) |
      static_cast<uint32_t>(low));
}

// One shard's first n rows: w[r * pl + i] is word r of row i. Returns the
// sum of ns over the allowed rows (wrapping, as NumPy's int64 sum does).
template <Format F>
uint64_t unpack_shard(const int32_t* w, int64_t pl, int64_t n, int64_t now_us,
                      double retry_denied, double reset_all,
                      const int64_t* ns, uint8_t* allowed, int64_t* remaining,
                      double* retry, double* reset) {
  uint64_t mass = 0;
  for (int64_t i = 0; i < n; ++i) {
    const bool ok = w[i] != 0;
    allowed[i] = ok;
    remaining[i] = w[pl + i];
    if (F == kWindow) {
      retry[i] = ok ? 0.0 : retry_denied;
    } else {
      retry[i] = static_cast<double>(join_words(w[2 * pl + i],
                                                w[3 * pl + i])) / 1e6;
    }
    if (F == kDense) {
      const uint64_t at = static_cast<uint64_t>(now_us) +
          static_cast<uint64_t>(join_words(w[4 * pl + i], w[5 * pl + i]));
      reset[i] = static_cast<double>(static_cast<int64_t>(at)) / 1e6;
    } else {
      reset[i] = reset_all;
    }
    if (ns != nullptr && ok) mass += static_cast<uint64_t>(ns[i]);
  }
  return mass;
}

constexpr int kFormatRows[] = {2, 4, 6};  // WINDOW_ROWS, BUCKET_ROWS, DENSE_ROWS

// A buffer view released on every exit.
struct View {
  Py_buffer view{};
  bool taken = false;
  bool take(PyObject* obj, int flags) {
    taken = PyObject_GetBuffer(obj, &view, flags) == 0;
    return taken;
  }
  int64_t len() const { return static_cast<int64_t>(view.len); }
  template <typename T> T* as() const { return static_cast<T*>(view.buf); }
  ~View() { if (taken) PyBuffer_Release(&view); }
};

}  // namespace

// unpack_columns(format, words, shards, tail, b, now_us, retry_denied,
//                reset_all, ns | None, allowed, remaining, retry, reset)
//   -> int, the sum of ns over the allowed rows (0 without ns)
// words: the fetched int32[shards * (rows * pl + tail)]; the four outputs
// are the caller's np.empty(b) arrays (bool, int64, float64, float64); ns
// int64[b]. Row i of the batch is word i % pl of shard i / pl
// (sketch_kernels.result_rows' order). retry_denied / reset_all are the
// scalars the windowed formats' twins compute in Python; now_us is the
// dense format's. Buffers, not addresses: every length is checked here.
static PyObject* unpack_columns(PyObject*, PyObject* args) {
  int format;
  long long shards, tail, b, now_us;
  double retry_denied, reset_all;
  PyObject *words_obj, *ns_obj, *out_obj[4];
  if (!PyArg_ParseTuple(args, "iOLLLLddOOOOO", &format, &words_obj, &shards,
                        &tail, &b, &now_us, &retry_denied, &reset_all, &ns_obj,
                        &out_obj[0], &out_obj[1], &out_obj[2], &out_obj[3])) {
    return nullptr;
  }
  View words, ns, allowed, remaining, retry, reset;
  if (!words.take(words_obj, PyBUF_SIMPLE) ||
      (ns_obj != Py_None && !ns.take(ns_obj, PyBUF_SIMPLE)) ||
      !allowed.take(out_obj[0], PyBUF_WRITABLE) ||
      !remaining.take(out_obj[1], PyBUF_WRITABLE) ||
      !retry.take(out_obj[2], PyBUF_WRITABLE) ||
      !reset.take(out_obj[3], PyBUF_WRITABLE)) {
    return nullptr;
  }
  if (format < kWindow || format > kDense || shards < 1 || tail < 0 || b < 0) {
    PyErr_SetString(PyExc_ValueError, "unpack_columns: bad format or shape");
    return nullptr;
  }
  const int64_t rows = kFormatRows[format];
  const int64_t per = words.len() / 4 / shards;
  const int64_t pl = (per - tail) / rows;
  if (words.len() != shards * per * 4 || per != rows * pl + tail ||
      b > shards * pl || (ns.taken && ns.len() != b * 8) ||
      allowed.len() != b || remaining.len() != b * 8 ||
      retry.len() != b * 8 || reset.len() != b * 8) {
    PyErr_SetString(PyExc_ValueError,
                    "unpack_columns: buffer lengths do not fit the format");
    return nullptr;
  }
  uint64_t mass = 0;
  for (int64_t s = 0, at = 0; at < b; ++s, at += pl) {
    const int32_t* w = words.as<const int32_t>() + s * per;
    const int64_t n = b - at < pl ? b - at : pl;
    const int64_t* n_at = ns.taken ? ns.as<const int64_t>() + at : nullptr;
    const auto pass = format == kWindow ? unpack_shard<kWindow>
                      : format == kBucket ? unpack_shard<kBucket>
                                          : unpack_shard<kDense>;
    mass += pass(w, pl, n, now_us, retry_denied, reset_all, n_at,
                 allowed.as<uint8_t>() + at, remaining.as<int64_t>() + at,
                 retry.as<double>() + at, reset.as<double>() + at);
  }
  return PyLong_FromLongLong(static_cast<int64_t>(mass));
}

static PyMethodDef kMethods[] = {
    {"hash_keylist", hash_keylist, METH_VARARGS,
     "Hash a list of str into the uint64 buffer at out_addr."},
    {"unpack_columns", unpack_columns, METH_VARARGS,
     "BatchResult's four columns from a step's packed int32 buffer."},
    {nullptr, nullptr, 0, nullptr},
};

static struct PyModuleDef kModule = {
    PyModuleDef_HEAD_INIT, "_hasher",
    "Native bulk string hasher (see hasher.cpp).", -1, kMethods,
    nullptr, nullptr, nullptr, nullptr,
};

PyMODINIT_FUNC PyInit__hasher(void) { return PyModule_Create(&kModule); }
