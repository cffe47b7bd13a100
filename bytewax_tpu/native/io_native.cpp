// Native host-side IO for the columnar fast path.
//
// The reference's engine is native (Rust/Timely); here the native
// surface is the host data plane that feeds the TPU: a zero-copy text
// parser turning 1BRC-style "station;-12.3\n" bytes into
// dictionary-encoded (key_id, deci-degree) columns, plus a generic
// newline chunker.  Python binds via ctypes (build: see
// bytewax_tpu/native/__init__.py).
//
// Reference workload: /root/reference/examples/1brc.py (the reference
// parses per-line in Python; this parser feeds the same rows to the
// device from one thread at 18 ns a 13.8-byte row, 750-770 MB/s, on
// the host of a v5e chip: a 10 M-row file, PERF.md section 6, PR 36;
// 51-52 ns and 265-270 MB/s before it).

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// Eight bytes as a little-endian word, whatever the host's order.
inline uint64_t load8(const char* p) {
  uint64_t w;
  std::memcpy(&w, p, 8);
#if __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
  w = __builtin_bswap64(w);
#endif
  return w;
}

// One step of the name hash over a word of up to eight name bytes,
// the bytes past the name's end zero.  A name hashes as its full
// eight-byte words in order, then its last zero to seven bytes as one
// more word: `hash_bytes` and the parser's word-at-a-time scan both
// feed it exactly so.
inline uint64_t hash_step(uint64_t h, uint64_t w) {
  h = (h ^ w) * 0x9E3779B97F4A7C15ull;
  return h ^ (h >> 32);
}

// The same hash a byte at a time, from state `h` at a word boundary:
// where fewer than eight bytes may be read.
inline uint64_t hash_bytes(uint64_t h, const char* s, size_t n) {
  uint64_t w = 0;
  unsigned i = 0;
  for (size_t k = 0; k < n; ++k) {
    w |= static_cast<uint64_t>(static_cast<unsigned char>(s[k])) << (8 * i);
    if (++i == 8) {
      h = hash_step(h, w);
      w = 0;
      i = 0;
    }
  }
  return hash_step(h, w);
}

// Incrementally-grown string dictionary: ids are assigned in first-
// sight order and never change (downstream device state keys on id
// identity across batches).  An open-addressed table (power-of-two
// size, linear probing, grown at half full) over one byte arena of
// names: a lookup compares hash, length, then bytes, and nothing is
// allocated but on a first sight.  Not safe to share between threads.
struct VocabSet {
  struct Slot {
    uint32_t hash;
    int32_t id;  // -1: empty
    uint32_t off;
    uint32_t len;
  };
  std::vector<Slot> slots;
  std::vector<char> arena;
  std::vector<uint32_t> starts;  // id -> offset; its end is the next start

  VocabSet() : slots(4096, Slot{0, -1, 0, 0}) {}

  int32_t size() const { return static_cast<int32_t>(starts.size()); }

  int32_t intern(const char* s, size_t n, uint64_t h64) {
    const uint32_t h = static_cast<uint32_t>(h64);
    const size_t mask = slots.size() - 1;
    size_t i = h & mask;
    for (;; i = (i + 1) & mask) {
      const Slot& sl = slots[i];
      if (sl.id < 0) return insert(i, s, n, h);
      if (sl.hash == h && sl.len == n &&
          std::memcmp(arena.data() + sl.off, s, n) == 0) {
        return sl.id;
      }
    }
  }

  // A first sight, out of line so the lookup's loop stays small.
  __attribute__((noinline)) int32_t insert(size_t i, const char* s, size_t n,
                                           uint32_t h) {
    const int32_t id = size();
    const uint32_t off = static_cast<uint32_t>(arena.size());
    arena.insert(arena.end(), s, s + n);
    starts.push_back(off);
    slots[i] = Slot{h, id, off, static_cast<uint32_t>(n)};
    if (starts.size() * 2 > slots.size()) grow();
    return id;
  }

  void grow() {
    std::vector<Slot> old(slots.size() * 2, Slot{0, -1, 0, 0});
    old.swap(slots);
    const size_t mask = slots.size() - 1;
    for (const Slot& sl : old) {
      if (sl.id < 0) continue;
      size_t i = sl.hash & mask;
      while (slots[i].id >= 0) i = (i + 1) & mask;
      slots[i] = sl;
    }
  }

  int32_t get(int32_t i, char* out, int32_t cap) const {
    if (i < 0 || i >= size()) return -1;
    const uint32_t off = starts[i];
    const uint32_t end = i + 1 < size()
                             ? starts[i + 1]
                             : static_cast<uint32_t>(arena.size());
    const int32_t n = static_cast<int32_t>(end - off);
    if (n > cap) return -n;
    std::memcpy(out, arena.data() + off, n);
    return n;
  }
};

struct BrcParser {
  VocabSet vocab;
};

// Word tokenizer for the wordcount fast path: splits lowered text on
// the same separator set as the Python-tier regex
// [^\s!,.?":;0-9]+ (models/wordcount.py), restricted to ASCII
// semantics — callers route non-ASCII lines through the Python
// regex (bytes >= 0x80 are treated as word chars here, identical to
// the regex for ASCII-whitespace-separated text).
struct WordTokenizer {
  VocabSet vocab;
  bool stop[256] = {};

  WordTokenizer() {
    // Mirrors TOKEN_RE in bytewax_tpu/ops/text.py: ASCII \s per
    // Python (space, \t-\r, and the \x1c-\x1f separators) plus the
    // listed punctuation and digits.  Keep the three in sync (the
    // parity test covers the edges).
    for (int c : {(int)' ', (int)'\t', (int)'\n', (int)'\r', (int)'\v',
                  (int)'\f', 0x1c, 0x1d, 0x1e, 0x1f, (int)'!', (int)',',
                  (int)'.', (int)'?', (int)'"', (int)':', (int)';'}) {
      stop[c] = true;
    }
    for (int c = '0'; c <= '9'; ++c) stop[c] = true;
  }
};

}  // namespace

extern "C" {

BrcParser* brc_parser_new() { return new BrcParser(); }

void brc_parser_free(BrcParser* p) { delete p; }

int32_t brc_vocab_size(const BrcParser* p) {
  return p->vocab.size();
}

int32_t brc_vocab_get(const BrcParser* p, int32_t i, char* out, int32_t cap) {
  return p->vocab.get(i, out, cap);
}

WordTokenizer* wc_new() { return new WordTokenizer(); }

void wc_free(WordTokenizer* p) { delete p; }

int32_t wc_vocab_size(const WordTokenizer* p) {
  return p->vocab.size();
}

int32_t wc_vocab_get(const WordTokenizer* p, int32_t i, char* out,
                     int32_t cap) {
  return p->vocab.get(i, out, cap);
}

// Tokenize a text buffer into dictionary-encoded word ids: one pass,
// one hash lookup per word.  Returns tokens written, or -1 when
// `cap` is too small.
int64_t wc_tokenize(WordTokenizer* p, const char* buf, int64_t len,
                    int32_t* ids, int64_t cap) {
  int64_t n = 0;
  const char* cur = buf;
  const char* end = buf + len;
  while (cur < end) {
    while (cur < end && p->stop[static_cast<unsigned char>(*cur)]) ++cur;
    if (cur >= end) break;
    const char* start = cur;
    while (cur < end && !p->stop[static_cast<unsigned char>(*cur)]) ++cur;
    if (n >= cap) return -1;
    const size_t len = cur - start;
    ids[n++] = p->vocab.intern(start, len, hash_bytes(0, start, len));
  }
  return n;
}

// Find the last newline in [buf, buf+len); returns the index one past
// it (the safe chunk split point), or 0 if none.
int64_t last_line_end(const char* buf, int64_t len) {
  for (int64_t i = len - 1; i >= 0; --i) {
    if (buf[i] == '\n') return i + 1;
  }
  return 0;
}

// Newlines in [buf, buf+len): a chunk can hold that many rows and one
// (a last line with no newline), which sizes the parser's columns.
int64_t count_newlines(const char* buf, int64_t len) {
  int64_t n = 0;
  // In blocks, so the inner sum stays 32 bits wide and vectorizes.
  for (int64_t i = 0; i < len; i += 4096) {
    const int64_t m = len - i < 4096 ? len - i : 4096;
    uint32_t k = 0;
    for (int64_t j = 0; j < m; ++j) k += buf[i + j] == '\n';
    n += k;
  }
  return n;
}

// Parse "station;temp\n" rows from buf (which must end on a line
// boundary) into dictionary-encoded columns, emitted as int16
// deci-degrees.  Returns rows written, or -1 on malformed input (a
// reading with a byte that is no digit and no '.', or with no digit).
//
// A row is chosen its way from its own bytes.  The name is hashed as
// its ';' is looked for, eight bytes a load (the has-zero-byte test
// on `word ^ ";;;;;;;;"`), and a byte at a time where fewer than
// eight are left in the buffer.  A reading in one of 1BRC's two
// shapes, [-]d.d or [-]dd.d followed by '\n' or the buffer's end, is
// taken from one word with no branch on its shape; any other takes
// the general loop (any digits, every '.' skipped), which
// `*general_rows` counts.
int64_t brc_parse_chunk(BrcParser* p, const char* buf, int64_t len,
                        int32_t* ids, int16_t* temps, int64_t cap,
                        int64_t* general_rows) {
  constexpr uint64_t kSemi = 0x3B3B3B3B3B3B3B3Bull;
  constexpr uint64_t kLow = 0x0101010101010101ull;
  constexpr uint64_t kHigh = 0x8080808080808080ull;
  int64_t rows = 0;
  const char* cur = buf;
  const char* end = buf + len;
  *general_rows = 0;
  while (cur < end && rows < cap) {
    const char* s = cur;
    uint64_t h = 0;
    for (;;) {
      if (end - s < 8) {
        const char* semi =
            static_cast<const char*>(std::memchr(s, ';', end - s));
        if (semi == nullptr) return rows;
        h = hash_bytes(h, s, semi - s);
        s = semi;
        break;
      }
      const uint64_t w = load8(s);
      const uint64_t x = w ^ kSemi;
      const uint64_t zero = (x - kLow) & ~x & kHigh;
      if (zero) {
        // The lowest flagged byte is the first ';': k name bytes of
        // this word come before it.
        const unsigned k = static_cast<unsigned>(__builtin_ctzll(zero)) >> 3;
        h = hash_step(h, w & ~(~0ull << (8 * k)));
        s += k;
        break;
      }
      h = hash_step(h, w);
      s += 8;
    }
    // Station id: one table probe per row; insert on first sight.
    const int32_t id = p->vocab.intern(cur, s - cur, h);

    // The reading, from the eight bytes behind the ';' (the buffer's
    // end reads as newlines): the sign shifted out, a d.d moved up a
    // byte behind a '0', so both shapes read "dd.d\n" from byte 0.
    const char* t = s + 1;
    uint64_t w;
    if (end - t >= 8) {
      w = load8(t);
    } else {
      char pad[8];
      std::memset(pad, '\n', sizeof pad);
      std::memcpy(pad, t, end - t);
      w = load8(pad);
    }
    const uint64_t neg = (w & 0xFF) == '-';
    w >>= 8 * neg;
    const uint64_t one = ((w >> 8) & 0xFF) == '.';  // d.d
    const uint64_t y =
        ((w << (8 * one)) | ('0' & (0 - one))) ^ 0x0A302E3030ull;
    // y: the digits' values in bytes 0, 1 and 3, zero where the '.'
    // and the '\n' stood; anything else is no fixed shape.
    int32_t v;
    if (((y & 0xFF00FF0000ull) |
         ((y | (y + 0x76007676ull)) & 0x80008080ull)) == 0) {
      v = static_cast<int32_t>((y & 0xFF) * 100 + ((y >> 8) & 0xFF) * 10 +
                               ((y >> 24) & 0xFF));
      cur = t + (neg + 5 - one);
    } else {
      const char* nl =
          static_cast<const char*>(std::memchr(t, '\n', end - t));
      if (nl == nullptr) nl = end;
      if (neg) ++t;
      v = 0;
      bool ok = false;
      for (; t < nl; ++t) {
        const char c = *t;
        if (c >= '0' && c <= '9') {
          v = v * 10 + (c - '0');
          ok = true;
        } else if (c != '.') {
          return -1;
        }
      }
      if (!ok) return -1;
      ++*general_rows;
      cur = nl + 1;
    }
    const int32_t sign = -static_cast<int32_t>(neg);
    temps[rows] = static_cast<int16_t>((v ^ sign) - sign);
    ids[rows] = id;
    ++rows;
  }
  return rows;
}

// Generic newline splitter: writes the byte offsets of line starts
// into `offsets` (up to cap); returns the count.  Used by the
// columnar file feeder to slice micro-batches without Python loops.
int64_t line_offsets(const char* buf, int64_t len, int64_t* offsets,
                     int64_t cap) {
  int64_t n = 0;
  const char* cur = buf;
  const char* end = buf + len;
  while (cur < end && n < cap) {
    offsets[n++] = cur - buf;
    const char* nl = static_cast<const char*>(memchr(cur, '\n', end - cur));
    if (nl == nullptr) break;
    cur = nl + 1;
  }
  return n;
}

}  // extern "C"
