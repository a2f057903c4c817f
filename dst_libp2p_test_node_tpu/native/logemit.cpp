// Native log emitter (loaded via ctypes, see runtime/native_logemit.py).
//
// format_block: one message's worth of awk-consumable latencies lines:
//   shadow.data/hosts/peer<pid>/main.1000.stdout:<lineno>:<msgId> milliseconds: <ms>
// format_shadowlog: one '[node]' heartbeat line a peer, the input of
// summary_shadowlog.awk (runtime/bandwidth.py has the layout and the
// Python formatter that writes the same bytes).
// The reference gets these lines for free from grep over per-process stdout
// files (shadow/run.sh:61); with a million simulated peers in one process,
// Python string formatting becomes the bottleneck, hence this C++ hot path
// (SURVEY.md §2 native-component note).
//
// Build: g++ -O2 -shared -fPIC -o liblogemit.so logemit.cpp

#include <cstdint>
#include <cstring>

namespace {

// fast unsigned integer -> ascii, returns chars written
inline int u64_to_ascii(uint64_t v, char *out) {
  char tmp[20];
  int n = 0;
  do {
    tmp[n++] = static_cast<char>('0' + (v % 10));
    v /= 10;
  } while (v != 0);
  for (int i = 0; i < n; ++i) out[i] = tmp[n - 1 - i];
  return n;
}

inline int i64_to_ascii(int64_t v, char *out) {
  if (v < 0) {
    out[0] = '-';
    return 1 + u64_to_ascii(static_cast<uint64_t>(-v), out + 1);
  }
  return u64_to_ascii(static_cast<uint64_t>(v), out);
}

constexpr char kPrefix[] = "shadow.data/hosts/peer";
constexpr char kStdout[] = "/main.1000.stdout:";
constexpr char kMillis[] = " milliseconds: ";

constexpr char kNode[] = " n/a shadow heartbeat [node] heartbeat;";
// rx,tx are followed by three pad fields and the two all-zero localhost
// blocks of 12 flags each (summary_shadowlog.awk:3-8)
constexpr char kPadAndLocalhost[] =
    ",0,0,0;0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,";
constexpr int kShadowFields = 14;  // 7 non-zero flags x (remote in, remote out)

// one remote block: pkt,bytes,ctrl_pkt,ctrl_hdr,0,0,data_pkt,data_hdr,
// data_bytes,0,0,0 from its seven non-zero flags
inline char *remote_block(const long long *f, char *p) {
  for (int k = 0; k < 4; ++k) {
    p += i64_to_ascii(f[k], p);
    *p++ = ',';
  }
  std::memcpy(p, "0,0,", 4);
  p += 4;
  for (int k = 4; k < 7; ++k) {
    p += i64_to_ascii(f[k], p);
    *p++ = ',';
  }
  std::memcpy(p, "0,0,0", 5);
  return p + 5;
}

}  // namespace

extern "C" {

// Returns bytes written, or -1 if the output buffer is too small.
long long format_block(unsigned long long msg_id, const long long *peers,
                       const long long *linenos, const long long *delays,
                       long long count, char *out, long long capacity) {
  char msg_buf[21];
  const int msg_len = u64_to_ascii(msg_id, msg_buf);
  char *p = out;
  const char *end = out + capacity;
  // worst case line: 57 fixed chars + 3x21-char signed int64 + 20-char msgId
  for (long long i = 0; i < count; ++i) {
    if (end - p < 160) return -1;
    std::memcpy(p, kPrefix, sizeof(kPrefix) - 1);
    p += sizeof(kPrefix) - 1;
    p += i64_to_ascii(peers[i], p);
    std::memcpy(p, kStdout, sizeof(kStdout) - 1);
    p += sizeof(kStdout) - 1;
    p += i64_to_ascii(linenos[i], p);
    *p++ = ':';
    std::memcpy(p, msg_buf, msg_len);
    p += msg_len;
    std::memcpy(p, kMillis, sizeof(kMillis) - 1);
    p += sizeof(kMillis) - 1;
    p += i64_to_ascii(delays[i], p);
    *p++ = '\n';
  }
  return p - out;
}

// One line a peer, peers 0..count-1 in order:
//   <head><i> n/a shadow heartbeat [node] heartbeat;<rx>,<tx>,0,0,0;<48 flags>
// `fields` is (count, 14) row-major: the remote-in block's seven non-zero
// flags, then the remote-out block's; rx and tx are each block's bytes flag.
// Returns bytes written, or -1 if the output buffer is too small.
long long format_shadowlog(const char *head, long long head_len,
                           const long long *fields, long long count, char *out,
                           long long capacity) {
  char *p = out;
  const char *end = out + capacity;
  // worst case line: head + 17 numbers of 21 chars + 137 fixed chars
  const long long line_max = head_len + 512;
  for (long long i = 0; i < count; ++i) {
    if (end - p < line_max) return -1;
    const long long *f = fields + i * kShadowFields;
    std::memcpy(p, head, head_len);
    p += head_len;
    p += i64_to_ascii(i, p);
    std::memcpy(p, kNode, sizeof(kNode) - 1);
    p += sizeof(kNode) - 1;
    p += i64_to_ascii(f[1], p);
    *p++ = ',';
    p += i64_to_ascii(f[8], p);
    std::memcpy(p, kPadAndLocalhost, sizeof(kPadAndLocalhost) - 1);
    p += sizeof(kPadAndLocalhost) - 1;
    p = remote_block(f, p);
    *p++ = ',';
    p = remote_block(f + 7, p);
    *p++ = '\n';
  }
  return p - out;
}

}  // extern "C"
