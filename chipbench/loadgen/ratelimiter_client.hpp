// The wire constants of the ratelimiter_tpu serving protocol, as the
// benchmark's load generator needs them.
//
// Cut from clients/cpp/ratelimiter_client.hpp (PR 22): the frame types
// and nothing else. The blocking Client class, the unix-socket path and
// the shm transport stay with the program; the benchmark speaks TCP on
// the loopback and pipelines its own frames. A later PR may change the
// program's client; it may not change this file.
//
// Frame layout (little-endian): u32 payload_length (not counting these
// four bytes) | u8 type | u64 request_id | body.
//   ALLOW_BATCH  (5):   u32 count, count x {u32 n, u16 key_len, key}
//   ALLOW_HASHED (11):  u32 count | u64 ids[count] | u32 ns[count]
//   RESULT_BATCH (133): i64 limit | u32 count | count x {u8 flags
//                       (bit0 allowed, bit1 fail_open), i64 remaining,
//                       f64 retry_after, f64 reset_at}
//   RESULT_HASHED(136): u8 batch_flags (bit1 fail_open, whole batch) |
//                       i64 limit | u32 count | u8 bits[ceil(count/8)] |
//                       i64 remaining[count] | f64 retry[count] |
//                       f64 reset[count]
//   ERROR        (255): u16 code | u16 msg_len | msg; answers the whole
//                       request frame

#pragma once

#include <cstdint>

namespace rltpu {

enum : uint8_t {
  T_ALLOW_BATCH = 5,
  T_ALLOW_HASHED = 11,
  T_RESULT_BATCH = 133,
  T_RESULT_HASHED = 136,
  T_ERROR = 255,
};

constexpr int RESULT_BATCH_ITEM = 25;  // bytes per RESULT_BATCH item

}  // namespace rltpu
