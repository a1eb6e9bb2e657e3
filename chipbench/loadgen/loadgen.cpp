// The benchmark's load generator (chipbench). One general generator; a
// traffic mix is a data file whose fields arrive here as options.
//
// Started from clients/cpp/loadgen.cpp (PR 22 copy): pipelined raw
// frames on TCP sockets, decisions and policy answers counted from the
// reply bytes, the door's splitmix64 routing carried bit for bit. Added:
// a seed; an open loop timed from the instant each frame was DUE, with
// the generator's own lateness reported; a Zipf sampler (alias table);
// a fixed rank -> id permutation; per-second slices; allowed tallies
// for the top ranks; string and hashed lanes; connections multiplexed
// over at most 4 epoll threads. Dropped: the unix-socket and shm
// transports.
//
// Options (each `--name value`; defaults in brackets):
//   --host [127.0.0.1] --port P --seed [1]
//   --lane hashed|string [hashed]   ALLOW_HASHED u64 ids | ALLOW_BATCH "user:<id>"
//   --frame-keys [4096] --conns [4]  (generator threads = min(4, conns))
//   --loop closed|open [closed]
//   --inflight [4]                  closed loop: frames in flight per connection
//   --rate R                        open loop: DECISIONS per second, all connections
//   --arrival poisson|uniform [poisson]
//   --keys N  --zipf-s [1.1]  --id-base [0]   (s = 0 is uniform; rank -> id is a
//                                   permutation fixed by N alone, not by the seed)
//   --cost-n [1]                    the n of every decision
//   --slices [1]                    tally decisions per owning slice (splitmix64(id) % slices)
//   --start-at T                    CLOCK_MONOTONIC seconds at which warm-up starts [now + 0.2]
//   --await-start 1                 the schedule and the port are GIVEN once the tables
//                                   are built (below); excludes --port and --start-at
//   --warmup [3] --seconds [10] --drain [2]
//   --dump-ids N                    print N sampled "rank id" lines and exit (no network)
// Output: one JSON object on stdout, the last line.
//
// The tables (alias table, permutation) take 1.6 s at 20 M keys and 7-9 s
// at 80-100 M, so the clock starts after them. With --await-start 1 the
// generator prints {"line": "ready", "keys", "build_s", "t_ready",
// "peak_rss_bytes"} and reads ONE line from stdin, "<T> <port>": the
// instant warm-up starts (CLOCK_MONOTONIC seconds) and the server's port
// (the generator is started before the server has one). It answers
// {"line": "schedule", "t_start", "t_window_start", "t_window_end"}:
// whoever drives it reads the window from those words. An instant already
// past is refused; with --start-at (a hand run) so are tables that are
// ready only after start-at + warmup. Both exit 4, naming the keys and
// the build's seconds: no path measures a window the generator was not
// sending in.

#include <arpa/inet.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/prctl.h>
#include <sys/resource.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <iostream>
#include <map>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "ratelimiter_client.hpp"

namespace {

double now_s() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

// splitmix64 finalizer — BIT-IDENTICAL to ops/hashing.splitmix64 and the
// native door's router (native/server.cpp): the per-slice tally must
// agree with where the server sends each id.
inline uint64_t splitmix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

// xoshiro256**, seeded through splitmix64 from (seed, stream).
struct Rng {
  uint64_t s[4];
  Rng(uint64_t seed, uint64_t stream) {
    uint64_t x = seed * 0x9e3779b97f4a7c15ULL + stream;
    for (auto& v : s) v = splitmix64(x++);
  }
  static uint64_t rotl(uint64_t x, int k) { return (x << k) | (x >> (64 - k)); }
  uint64_t next() {
    uint64_t r = rotl(s[1] * 5, 7) * 9, t = s[1] << 17;
    s[2] ^= s[0]; s[3] ^= s[1]; s[1] ^= s[2]; s[0] ^= s[3];
    s[2] ^= t; s[3] = rotl(s[3], 45);
    return r;
  }
  double unit() { return (double)(next() >> 11) * 0x1.0p-53; }  // [0, 1)
  uint64_t below(uint64_t n) { return (uint64_t)(((__uint128_t)next() * n) >> 64); }
};

// Zipf(s) over ranks 0..n-1 (rank 0 hottest) as a Vose alias table:
// exact probabilities p_k ~ (k+1)^-s, one table for every thread.
struct Zipf {
  std::vector<double> prob;
  std::vector<uint32_t> alias;
  void build(uint32_t n, double s) {
    std::vector<double> p(n);
    double sum = 0;
    for (uint32_t k = 0; k < n; ++k) sum += p[k] = std::pow((double)k + 1.0, -s);
    prob.assign(n, 1.0);
    alias.resize(n);
    std::vector<uint32_t> small, large;
    for (uint32_t k = 0; k < n; ++k) {
      p[k] = p[k] / sum * n;
      alias[k] = k;
      (p[k] < 1.0 ? small : large).push_back(k);
    }
    while (!small.empty() && !large.empty()) {
      uint32_t lo = small.back(), hi = large.back();
      small.pop_back();
      prob[lo] = p[lo];
      alias[lo] = hi;
      p[hi] = p[hi] + p[lo] - 1.0;
      if (p[hi] < 1.0) { large.pop_back(); small.push_back(hi); }
    }
  }
  uint32_t sample(Rng& r) const {
    uint32_t i = (uint32_t)r.below(prob.size());
    return r.unit() < prob[i] ? i : alias[i];
  }
};

struct Options {
  std::string host = "127.0.0.1", lane = "hashed", loop = "closed";
  std::string arrival = "poisson";
  int port = 0, frame_keys = 4096, conns = 4, inflight = 4, slices = 1;
  int threads = 0;     // min(MAX_THREADS, conns)
  int top_ranks = 0;   // min(TOP_RANKS, keys)
  int await_start = 0;
  uint32_t keys = 0, cost_n = 1;
  uint64_t seed = 1, id_base = 0, dump_ids = 0;
  double rate = 0, zipf_s = 1.1, start_at = 0, warmup = 3, seconds = 10, drain = 2;
};

constexpr int MAX_THREADS = 4;  // more would fight the server for the host's cores
constexpr int TOP_RANKS = 64;   // hottest ranks whose allowed replies are tallied
constexpr uint64_t PERM_SEED = 12;  // the rank -> id permutation's own seed

struct Shared {
  Options o;
  Zipf zipf;
  std::vector<uint32_t> perm;  // rank -> id offset
  double t_start, t_win0, t_win1, t_end;
  int n_slices_s;  // per-second slices in the window
};

struct Pending {
  double due = 0, sent = 0;
  uint32_t n = 0;
  bool in_window = false;
  std::vector<uint32_t> ranks;
};

struct Conn {
  int fd = -1;
  std::string out;  // unsent bytes
  size_t out_off = 0;
  std::string in;
  size_t in_off = 0;
  bool want_out = false;
  std::unordered_map<uint64_t, int> pending;  // request id -> slot
};

struct SecondSlice {
  uint64_t completed = 0, frames = 0, pending_at_end = 0;
  std::vector<float> lat_ms;
};

// Everything one generator thread counts; merged after the join.
struct Tally {
  // Inside the window.
  uint64_t sent = 0, sent_frames = 0, completed = 0, completed_frames = 0;
  uint64_t allowed = 0, policy = 0, error_frames = 0, error_decisions = 0;
  uint64_t unanswered = 0;
  // Whole run (warm-up, window and drain).
  uint64_t all_sent = 0, all_completed = 0, all_allowed = 0, all_policy = 0;
  uint64_t all_error_frames = 0;
  uint64_t backlog_max = 0;
  std::vector<SecondSlice> slices;
  std::vector<float> late_ms;
  std::vector<uint64_t> top_allowed, slice_sent;
  bool connect_failed = false, io_failed = false;
};

int connect_fd(const Options& o) {
  addrinfo hints{}, *res = nullptr;
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  std::string ps = std::to_string(o.port);
  if (getaddrinfo(o.host.c_str(), ps.c_str(), &hints, &res) != 0) return -1;
  int fd = socket(res->ai_family, res->ai_socktype, res->ai_protocol);
  if (fd < 0 || connect(fd, res->ai_addr, res->ai_addrlen) != 0) {
    freeaddrinfo(res);
    if (fd >= 0) close(fd);
    return -1;
  }
  freeaddrinfo(res);
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  fcntl(fd, F_SETFL, fcntl(fd, F_GETFL, 0) | O_NONBLOCK);
  return fd;
}

template <typename T>
void put(std::string& b, T v) { b.append((const char*)&v, sizeof(T)); }

class Worker {
 public:
  Worker(const Shared& sh, int wid, int n_conns)
      : sh_(sh), o_(sh.o), keys_(sh.o.seed, 2 * (uint64_t)wid),
        arrivals_(sh.o.seed, 2 * (uint64_t)wid + 1), conns_(n_conns) {
    t_.slices.resize((size_t)sh.n_slices_s);
    t_.top_allowed.assign((size_t)o_.top_ranks, 0);
    t_.slice_sent.assign((size_t)o_.slices, 0);
    hashed_ = o_.lane == "hashed";
    open_ = o_.loop == "open";
  }

  Tally& tally() { return t_; }

  void run() {
    prctl(PR_SET_TIMERSLACK, 1UL);
    ep_ = epoll_create1(0);
    for (size_t i = 0; i < conns_.size(); ++i) {
      conns_[i].fd = connect_fd(o_);
      if (conns_[i].fd < 0) { t_.connect_failed = true; return; }
      epoll_event ev{};
      ev.events = EPOLLIN;
      ev.data.u32 = (uint32_t)i;
      epoll_ctl(ep_, EPOLL_CTL_ADD, conns_[i].fd, &ev);
    }
    // Frames per second this thread owes (open loop).
    double fps = open_ ? o_.rate / o_.frame_keys *
                             ((double)conns_.size() / o_.conns) : 0;
    double next_due = sh_.t_start + (open_ ? gap(fps) : 0);
    bool primed = false;
    int last_slice = -1;
    std::vector<epoll_event> evs(256);
    std::vector<char> buf(1 << 18);
    size_t rr = 0;
    for (;;) {
      double now = now_s();
      if (now >= sh_.t_end) break;
      bool sending = now < sh_.t_win1;
      if (!sending && n_pending_ == 0) break;
      if (now >= sh_.t_start && sending) {
        if (open_) {
          while (next_due <= now && next_due < sh_.t_win1) {
            send_frame(conns_[rr++ % conns_.size()], next_due);
            next_due += gap(fps);
            now = now_s();
          }
        } else if (!primed) {
          primed = true;
          for (auto& c : conns_)
            for (int k = 0; k < o_.inflight; ++k) send_frame(c, now);
        }
      }
      int slice = slice_of(now);
      if (slice != last_slice) {
        if (last_slice >= 0) t_.slices[(size_t)last_slice].pending_at_end = n_pending_;
        last_slice = slice;
      }
      t_.backlog_max = std::max(t_.backlog_max, n_pending_);
      double wake = sh_.t_end;
      if (now < sh_.t_start) wake = sh_.t_start;
      else if (open_ && sending) wake = std::min(next_due, sh_.t_win1);
      else if (sending) wake = sh_.t_win1;
      double wait = std::max(0.0, wake - now);
      timespec ts{(time_t)wait, (long)((wait - std::floor(wait)) * 1e9)};
      int n = epoll_pwait2(ep_, evs.data(), (int)evs.size(), &ts, nullptr);
      if (n < 0 && errno != EINTR) { t_.io_failed = true; break; }
      for (int i = 0; i < n; ++i) {
        Conn& c = conns_[evs[(size_t)i].data.u32];
        if (evs[(size_t)i].events & EPOLLOUT) flush(c);
        if (evs[(size_t)i].events & (EPOLLIN | EPOLLHUP | EPOLLERR)) {
          if (!on_readable(c, buf)) { t_.io_failed = true; goto done; }
        }
      }
    }
  done:
    // What was sent inside the window and never answered.
    for (auto& c : conns_) {
      for (auto& kv : c.pending)
        if (pool_[(size_t)kv.second].in_window)
          t_.unanswered += pool_[(size_t)kv.second].n;
      if (c.fd >= 0) close(c.fd);
    }
    close(ep_);
  }

 private:
  double gap(double fps) {
    if (fps <= 0) return 1e9;
    if (o_.arrival == "uniform") return 1.0 / fps;
    return -std::log(1.0 - arrivals_.unit()) / fps;
  }

  int slice_of(double t) const {
    if (t < sh_.t_win0 || t >= sh_.t_win1) return -1;
    return std::min(sh_.n_slices_s - 1, (int)(t - sh_.t_win0));
  }

  void send_frame(Conn& c, double due) {
    int slot;
    if (free_.empty()) {
      slot = (int)pool_.size();
      pool_.emplace_back();
    } else {
      slot = free_.back();
      free_.pop_back();
    }
    Pending& p = pool_[(size_t)slot];
    const uint32_t count = (uint32_t)o_.frame_keys;
    p.due = due;
    p.n = count;
    p.in_window = due >= sh_.t_win0 && due < sh_.t_win1;
    p.ranks.resize(count);
    for (auto& r : p.ranks) r = sh_.zipf.sample(keys_);

    body_.clear();
    put<uint32_t>(body_, count);
    if (hashed_) {
      for (uint32_t r : p.ranks) put<uint64_t>(body_, id_of(r));
      for (uint32_t i = 0; i < count; ++i) put<uint32_t>(body_, o_.cost_n);
    } else {
      char key[64];
      for (uint32_t r : p.ranks) {
        int klen = snprintf(key, sizeof(key), "user:%llu",
                            (unsigned long long)id_of(r));
        put<uint32_t>(body_, o_.cost_n);
        put<uint16_t>(body_, (uint16_t)klen);
        body_.append(key, (size_t)klen);
      }
    }
    if (p.in_window && o_.slices > 1)
      for (uint32_t r : p.ranks)
        ++t_.slice_sent[splitmix64(id_of(r)) % (uint64_t)o_.slices];

    uint64_t rid = ++req_id_;
    put<uint32_t>(c.out, (uint32_t)(1 + 8 + body_.size()));
    c.out.push_back((char)(hashed_ ? rltpu::T_ALLOW_HASHED : rltpu::T_ALLOW_BATCH));
    put<uint64_t>(c.out, rid);
    c.out += body_;
    c.pending[rid] = slot;
    ++n_pending_;
    p.sent = now_s();
    flush(c);
    t_.all_sent += count;
    if (p.in_window) {
      t_.sent += count;
      ++t_.sent_frames;
      if (open_) t_.late_ms.push_back((float)((p.sent - due) * 1e3));
    }
  }

  uint64_t id_of(uint32_t rank) const { return o_.id_base + sh_.perm[rank]; }

  void flush(Conn& c) {
    while (c.out_off < c.out.size()) {
      ssize_t w = send(c.fd, c.out.data() + c.out_off, c.out.size() - c.out_off,
                       MSG_NOSIGNAL);
      if (w > 0) { c.out_off += (size_t)w; continue; }
      if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) break;
      if (w < 0 && errno == EINTR) continue;
      t_.io_failed = true;
      break;
    }
    bool left = c.out_off < c.out.size();
    if (!left) { c.out.clear(); c.out_off = 0; }
    if (left != c.want_out) {
      c.want_out = left;
      epoll_event ev{};
      ev.events = EPOLLIN | (left ? (uint32_t)EPOLLOUT : 0u);
      ev.data.u32 = (uint32_t)(&c - conns_.data());
      epoll_ctl(ep_, EPOLL_CTL_MOD, c.fd, &ev);
    }
  }

  bool on_readable(Conn& c, std::vector<char>& buf) {
    for (;;) {
      ssize_t r = recv(c.fd, buf.data(), buf.size(), 0);
      if (r > 0) {
        c.in.append(buf.data(), (size_t)r);
        if ((size_t)r < buf.size()) break;
        continue;
      }
      if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) break;
      if (r < 0 && errno == EINTR) continue;
      return false;  // closed by the server, or an error
    }
    while (c.in.size() - c.in_off >= 13) {
      const char* f = c.in.data() + c.in_off;
      uint32_t length;
      memcpy(&length, f, 4);
      if (c.in.size() - c.in_off < 4 + (size_t)length) break;
      on_frame(c, (uint8_t)f[4], f + 5, length);
      c.in_off += 4 + (size_t)length;
    }
    if (c.in_off == c.in.size()) { c.in.clear(); c.in_off = 0; }
    else if (c.in_off > (1u << 20)) { c.in.erase(0, c.in_off); c.in_off = 0; }
    return true;
  }

  void on_frame(Conn& c, uint8_t type, const char* p, uint32_t length) {
    uint64_t rid;
    memcpy(&rid, p, 8);
    const char* body = p + 8;
    auto it = c.pending.find(rid);
    if (it == c.pending.end()) return;
    int slot = it->second;
    Pending& pd = pool_[(size_t)slot];
    double t1 = now_s();
    // Closed loop: a reply counts where it ARRIVES. Open loop: a frame
    // counts where it was DUE, answered any time before the drain ends.
    bool timed = open_ ? pd.in_window : (t1 >= sh_.t_win0 && t1 < sh_.t_win1);
    int slice = slice_of(open_ ? pd.due : t1);
    bool answered = true;
    if (type == rltpu::T_RESULT_HASHED && length >= 9 + 13) {
      uint32_t count;
      memcpy(&count, body + 9, 4);
      bool policy = ((uint8_t)body[0] & 2) != 0;
      const uint8_t* bits = (const uint8_t*)body + 13;
      uint64_t allowed = 0;
      for (uint32_t i = 0; i < count && i < pd.n; ++i) {
        uint32_t a = (bits[i >> 3] >> (i & 7)) & 1;
        allowed += a;
        if (a && pd.ranks[i] < (uint32_t)o_.top_ranks) ++t_.top_allowed[pd.ranks[i]];
      }
      note(count, allowed, policy ? count : 0, timed);
    } else if (type == rltpu::T_RESULT_BATCH && length >= 9 + 12) {
      uint32_t count;
      memcpy(&count, body + 8, 4);
      const char* items = body + 12;
      uint64_t allowed = 0, policy = 0;
      for (uint32_t i = 0; i < count && i < pd.n; ++i) {
        uint8_t fl = (uint8_t)items[(size_t)i * rltpu::RESULT_BATCH_ITEM];
        allowed += fl & 1;
        policy += (fl >> 1) & 1;
        if ((fl & 1) && pd.ranks[i] < (uint32_t)o_.top_ranks) ++t_.top_allowed[pd.ranks[i]];
      }
      note(count, allowed, policy, timed);
    } else if (type == rltpu::T_ERROR) {
      ++t_.all_error_frames;
      if (timed) { ++t_.error_frames; t_.error_decisions += pd.n; }
      answered = false;
    } else {
      return;  // not a reply to a decision frame
    }
    if (timed && answered) {
      ++t_.completed_frames;
      if (slice >= 0) {
        SecondSlice& s = t_.slices[(size_t)slice];
        s.completed += pd.n;
        ++s.frames;
        s.lat_ms.push_back((float)((t1 - (open_ ? pd.due : pd.sent)) * 1e3));
      }
    }
    c.pending.erase(it);
    free_.push_back(slot);
    --n_pending_;
    // Closed loop: an error frame answers a request too; keep K in flight.
    if (!open_ && t1 < sh_.t_win1) send_frame(c, t1);
  }

  void note(uint64_t count, uint64_t allowed, uint64_t policy, bool timed) {
    t_.all_completed += count;
    t_.all_allowed += allowed;
    t_.all_policy += policy;
    if (timed) {
      t_.completed += count - policy;
      t_.allowed += allowed;
      t_.policy += policy;
    }
  }

  const Shared& sh_;
  const Options& o_;
  Rng keys_, arrivals_;
  std::vector<Conn> conns_;
  std::vector<Pending> pool_;
  std::vector<int> free_;
  std::string body_;
  Tally t_;
  uint64_t req_id_ = 0, n_pending_ = 0;
  int ep_ = -1;
  bool hashed_ = true, open_ = false;
};

double pct(const std::vector<float>& sorted, double p) {
  if (sorted.empty()) return 0.0;
  return sorted[std::min(sorted.size() - 1, (size_t)(p * (double)sorted.size()))];
}

void print_u64s(const char* name, const std::vector<uint64_t>& v) {
  std::printf("\"%s\": [", name);
  for (size_t i = 0; i < v.size(); ++i)
    std::printf("%s%llu", i ? ", " : "", (unsigned long long)v[i]);
  std::printf("]");
}

bool parse(int argc, char** argv, Options* o) {
  std::map<std::string, std::string> kv;
  for (int i = 1; i + 1 < argc; i += 2) {
    if (strncmp(argv[i], "--", 2) != 0) return false;
    kv[argv[i] + 2] = argv[i + 1];
  }
  if (argc % 2 == 0) return false;
  auto take = [&](const char* k, auto* dst, auto conv) {
    auto it = kv.find(k);
    if (it != kv.end()) { *dst = conv(it->second.c_str()); kv.erase(it); }
  };
  auto str = [](const char* s) { return std::string(s); };
  auto i32 = [](const char* s) { return atoi(s); };
  auto u32 = [](const char* s) { return (uint32_t)strtoul(s, nullptr, 10); };
  auto u64 = [](const char* s) { return (uint64_t)strtoull(s, nullptr, 10); };
  auto f64 = [](const char* s) { return atof(s); };
  take("host", &o->host, str); take("lane", &o->lane, str);
  take("loop", &o->loop, str); take("arrival", &o->arrival, str);
  take("port", &o->port, i32); take("frame-keys", &o->frame_keys, i32);
  take("conns", &o->conns, i32);
  take("inflight", &o->inflight, i32); take("slices", &o->slices, i32);
  take("await-start", &o->await_start, i32);
  take("keys", &o->keys, u32); take("cost-n", &o->cost_n, u32);
  take("seed", &o->seed, u64); take("id-base", &o->id_base, u64);
  take("dump-ids", &o->dump_ids, u64);
  take("rate", &o->rate, f64); take("zipf-s", &o->zipf_s, f64);
  take("start-at", &o->start_at, f64); take("warmup", &o->warmup, f64);
  take("seconds", &o->seconds, f64); take("drain", &o->drain, f64);
  for (auto& left : kv) std::fprintf(stderr, "unknown option --%s\n", left.first.c_str());
  if (!kv.empty()) return false;
  o->threads = std::min(MAX_THREADS, o->conns);
  bool ok = o->keys > 0 && o->frame_keys > 0 && o->conns > 0 && o->slices > 0 &&
            (o->lane == "hashed" || o->lane == "string") &&
            (o->loop == "closed" || o->loop == "open") &&
            (o->arrival == "poisson" || o->arrival == "uniform") &&
            (o->loop == "closed" ? o->inflight > 0 : o->rate > 0) &&
            (o->dump_ids > 0 || (o->await_start ? o->port == 0 && o->start_at == 0
                                                : o->port > 0));
  o->top_ranks = (int)std::min<uint32_t>(TOP_RANKS, o->keys);
  return ok;
}

// Peak resident set of this process so far (Linux: ru_maxrss is in KiB).
uint64_t peak_rss_bytes() {
  rusage ru{};
  getrusage(RUSAGE_SELF, &ru);
  return (uint64_t)ru.ru_maxrss * 1024;
}

int late(const Options& o, double build_s, const char* what) {
  std::fprintf(stderr, "loadgen: %s: the tables for %u keys took %.3f s to build; "
               "no window is measured that the generator was not sending in\n",
               what, o.keys, build_s);
  return 4;
}

}  // namespace

int main(int argc, char** argv) {
  Shared sh;
  if (!parse(argc, argv, &sh.o)) {
    std::fprintf(stderr, "usage: see the head of chipbench/loadgen/loadgen.cpp\n");
    return 2;
  }
  Options& o = sh.o;
  const double t_build0 = now_s();
  sh.zipf.build(o.keys, o.zipf_s);
  sh.perm.resize(o.keys);
  for (uint32_t i = 0; i < o.keys; ++i) sh.perm[i] = i;
  // Which id a rank has is the population's, not the run's: the seed draws
  // the request stream only. On a mesh the hottest ranks' slices decide the
  // load each slice gets (rank 0 alone is an eighth of the traffic), and
  // with a permutation per seed the completed rate followed that draw
  // (816 K .. 920 K decisions/s for imbalance 1.38 .. 1.22; PERF.md, PR 22).
  Rng shuffle(PERM_SEED, o.keys);
  for (uint32_t i = o.keys - 1; i > 0; --i)
    std::swap(sh.perm[i], sh.perm[shuffle.below((uint64_t)i + 1)]);

  if (o.dump_ids) {
    Rng r(o.seed, 0);
    for (uint64_t i = 0; i < o.dump_ids; ++i) {
      uint32_t rank = sh.zipf.sample(r);
      std::printf("%u %llu\n", rank, (unsigned long long)(o.id_base + sh.perm[rank]));
    }
    return 0;
  }

  const double t_ready = now_s(), build_s = t_ready - t_build0;
  if (o.await_start) {
    std::printf("{\"line\": \"ready\", \"keys\": %u, \"build_s\": %.6f, \"t_ready\": %.6f, "
                "\"peak_rss_bytes\": %llu}\n", o.keys, build_s, t_ready,
                (unsigned long long)peak_rss_bytes());
    std::fflush(stdout);
    std::string given;
    std::getline(std::cin, given);
    if (std::sscanf(given.c_str(), "%lf %d", &sh.t_start, &o.port) != 2 ||
        sh.t_start <= 0 || o.port <= 0) {
      std::fprintf(stderr, "loadgen: --await-start: no \"<T> <port>\" on stdin\n");
      return 2;
    }
    if (now_s() > sh.t_start) return late(o, build_s, "the start instant given on stdin is past");
  } else {
    sh.t_start = o.start_at > 0 ? o.start_at : t_ready + 0.2;
    if (t_ready > sh.t_start + o.warmup)
      return late(o, build_s, "ready after --start-at + --warmup");
  }
  sh.t_win0 = sh.t_start + o.warmup;
  sh.t_win1 = sh.t_win0 + o.seconds;
  sh.t_end = sh.t_win1 + o.drain;
  sh.n_slices_s = std::max(1, (int)std::ceil(o.seconds - 1e-9));
  if (o.await_start) {
    std::printf("{\"line\": \"schedule\", \"t_start\": %.6f, \"t_window_start\": %.6f, "
                "\"t_window_end\": %.6f}\n", sh.t_start, sh.t_win0, sh.t_win1);
    std::fflush(stdout);
  }

  std::vector<Worker*> workers;
  for (int i = 0; i < o.threads; ++i) {
    int n = o.conns / o.threads + (i < o.conns % o.threads ? 1 : 0);
    workers.push_back(new Worker(sh, i, n));
  }
  std::vector<std::thread> ts;
  for (Worker* w : workers) ts.emplace_back([w] { w->run(); });
  for (auto& t : ts) t.join();

  Tally sum;
  sum.slices.resize((size_t)sh.n_slices_s);
  sum.top_allowed.assign((size_t)o.top_ranks, 0);
  sum.slice_sent.assign((size_t)o.slices, 0);
  for (Worker* w : workers) {
    Tally& t = w->tally();
    sum.sent += t.sent; sum.sent_frames += t.sent_frames;
    sum.completed += t.completed; sum.completed_frames += t.completed_frames;
    sum.allowed += t.allowed; sum.policy += t.policy;
    sum.error_frames += t.error_frames; sum.error_decisions += t.error_decisions;
    sum.unanswered += t.unanswered;
    sum.all_sent += t.all_sent; sum.all_completed += t.all_completed;
    sum.all_allowed += t.all_allowed; sum.all_policy += t.all_policy;
    sum.all_error_frames += t.all_error_frames;
    sum.backlog_max += t.backlog_max;
    sum.connect_failed |= t.connect_failed; sum.io_failed |= t.io_failed;
    sum.late_ms.insert(sum.late_ms.end(), t.late_ms.begin(), t.late_ms.end());
    for (size_t i = 0; i < sum.slices.size(); ++i) {
      SecondSlice &a = sum.slices[i], &b = t.slices[i];
      a.completed += b.completed; a.frames += b.frames;
      a.pending_at_end += b.pending_at_end;
      a.lat_ms.insert(a.lat_ms.end(), b.lat_ms.begin(), b.lat_ms.end());
    }
    for (size_t i = 0; i < sum.top_allowed.size(); ++i) sum.top_allowed[i] += t.top_allowed[i];
    for (size_t i = 0; i < sum.slice_sent.size(); ++i) sum.slice_sent[i] += t.slice_sent[i];
  }
  if (sum.connect_failed) {
    std::fprintf(stderr, "loadgen: a connection to %s:%d failed\n", o.host.c_str(), o.port);
    return 1;
  }

  std::vector<float> all_lat, slice_p99;
  for (auto& s : sum.slices) {
    std::sort(s.lat_ms.begin(), s.lat_ms.end());
    all_lat.insert(all_lat.end(), s.lat_ms.begin(), s.lat_ms.end());
    if (!s.lat_ms.empty()) slice_p99.push_back((float)pct(s.lat_ms, 0.99));
  }
  std::sort(all_lat.begin(), all_lat.end());
  std::sort(slice_p99.begin(), slice_p99.end());
  std::sort(sum.late_ms.begin(), sum.late_ms.end());
  double p99_med = slice_p99.empty() ? 0.0
      : 0.5 * (slice_p99[(slice_p99.size() - 1) / 2] + slice_p99[slice_p99.size() / 2]);

  std::printf("{\"loop\": \"%s\", \"lane\": \"%s\", \"seed\": %llu, \"threads\": %d, "
              "\"conns\": %d, \"inflight\": %d, \"frame_keys\": %d, \"rate\": %.17g, "
              "\"keys\": %u, \"zipf_s\": %.17g, \"io_failed\": %s, ",
              o.loop.c_str(), o.lane.c_str(), (unsigned long long)o.seed, o.threads,
              o.conns, o.inflight, o.frame_keys, o.rate, o.keys, o.zipf_s,
              sum.io_failed ? "true" : "false");
  std::printf("\"build_s\": %.6f, \"t_ready\": %.6f, \"peak_rss_bytes\": %llu, "
              "\"t_start\": %.6f, \"t_window_start\": %.6f, \"t_window_end\": %.6f, "
              "\"window_s\": %.17g, \"run_s\": %.17g, ",
              build_s, t_ready, (unsigned long long)peak_rss_bytes(),
              sh.t_start, sh.t_win0, sh.t_win1, o.seconds, o.warmup + o.seconds);
  std::printf("\"sent\": %llu, \"sent_frames\": %llu, \"completed\": %llu, "
              "\"completed_frames\": %llu, \"allowed\": %llu, \"policy\": %llu, "
              "\"error_frames\": %llu, \"error_decisions\": %llu, \"unanswered\": %llu, ",
              (unsigned long long)sum.sent, (unsigned long long)sum.sent_frames,
              (unsigned long long)sum.completed, (unsigned long long)sum.completed_frames,
              (unsigned long long)sum.allowed, (unsigned long long)sum.policy,
              (unsigned long long)sum.error_frames, (unsigned long long)sum.error_decisions,
              (unsigned long long)sum.unanswered);
  std::printf("\"all\": {\"sent\": %llu, \"completed\": %llu, \"allowed\": %llu, "
              "\"policy\": %llu, \"error_frames\": %llu}, \"backlog_max_frames\": %llu, ",
              (unsigned long long)sum.all_sent, (unsigned long long)sum.all_completed,
              (unsigned long long)sum.all_allowed, (unsigned long long)sum.all_policy,
              (unsigned long long)sum.all_error_frames, (unsigned long long)sum.backlog_max);
  std::printf("\"latency_ms\": {\"n\": %zu, \"p50\": %.6f, \"p99\": %.6f, \"p999\": %.6f, "
              "\"max\": %.6f, \"p99_median_of_seconds\": %.6f}, ",
              all_lat.size(), pct(all_lat, 0.50), pct(all_lat, 0.99), pct(all_lat, 0.999),
              all_lat.empty() ? 0.0 : (double)all_lat.back(), p99_med);
  std::printf("\"gen_late_ms\": {\"n\": %zu, \"p50\": %.6f, \"p99\": %.6f, \"max\": %.6f}, ",
              sum.late_ms.size(), pct(sum.late_ms, 0.50), pct(sum.late_ms, 0.99),
              sum.late_ms.empty() ? 0.0 : (double)sum.late_ms.back());
  std::printf("\"per_second\": [");
  for (size_t i = 0; i < sum.slices.size(); ++i) {
    const SecondSlice& s = sum.slices[i];
    std::printf("%s{\"completed\": %llu, \"frames\": %llu, \"p50_ms\": %.6f, "
                "\"p99_ms\": %.6f, \"pending_frames\": %llu}", i ? ", " : "",
                (unsigned long long)s.completed, (unsigned long long)s.frames,
                pct(s.lat_ms, 0.50), pct(s.lat_ms, 0.99),
                (unsigned long long)s.pending_at_end);
  }
  std::printf("], ");
  print_u64s("top_allowed", sum.top_allowed);
  std::printf(", ");
  print_u64s("slice_sent", sum.slice_sent);
  std::printf("}\n");
  return sum.io_failed ? 1 : 0;
}
