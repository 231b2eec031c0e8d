// Native corpus ingest: file -> NUL-padded fixed-width line rows.
//
// TPU-native equivalent of the reference's host ingest (loadFile,
// reference MapReduce/src/main.cu:40-64): the reference reads with a
// getline loop into 204-byte structs; here the file is opened ONCE, read
// by pread a buffer at a time at the scan's own offset, and ONE line loop
// (scan_lines) finds each line's end with memchr and copies it straight
// into the caller's contiguous [max_lines, width] uint8 buffer, which the
// Python side hands to jnp.asarray with zero further copies.  Honors the
// same [line_start, line_end) node-shard slice (main.cu:47-54) and fixes
// the reference's dropped-final-line off-by-one (SURVEY.md Q1).
//
// pread and not a mapping: on the chip hosts' sandboxed kernel a page of a
// mapped memory file costs 7 us to fault in, so the mapped scan of a 180 MB
// file took 0.31 s where this one takes 0.05 (PERF.md section 6, PR 49).
//
// Exposed via a C ABI for ctypes (no pybind11 in this toolchain).

#include <climits>
#include <cstdio>
#include <cstdlib>
#include <cstring>

#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

// What a Source reads at a time.  A line longer than this keeps its first
// `width` bytes while the rest streams past, so a row is narrower than it.
const long kBuffer = 1 << 20;

// A regular file behind one descriptor: buf holds its bytes
// [base, base + got), and at_eof says that they reach the file's end.
struct Source {
  int fd;
  unsigned char* buf;
  long base, got;
  bool at_eof;
};

// Opens `path`; false for anything that is no regular file — no such file,
// a FIFO, a terminal, a device — which the callers report as their I/O
// error, and io/loader answers with its Python reader.  Asked by stat
// BEFORE the open: a FIFO opened and closed here would take its writer's
// reader away (EPIPE) before the Python reader opens it.
bool open_source(const char* path, Source* s) {
  struct stat st;
  if (stat(path, &st) != 0 || !S_ISREG(st.st_mode)) return false;
  const int fd = open(path, O_RDONLY | O_CLOEXEC);
  if (fd < 0) return false;
  *s = {fd, static_cast<unsigned char*>(std::malloc(kBuffer)), 0, 0, false};
  if (s->buf) return true;
  close(fd);
  return false;
}

void close_source(const Source& s) {
  close(s.fd);
  std::free(s.buf);
}

// Fills the buffer from file offset `off` on; false on a read error.
bool fill(Source* s, long off) {
  s->base = off;
  s->got = 0;
  s->at_eof = false;
  while (s->got < kBuffer && !s->at_eof) {
    const ssize_t n = pread(s->fd, s->buf + s->got,
                            static_cast<size_t>(kBuffer - s->got), off + s->got);
    if (n < 0) return false;
    s->at_eof = n == 0;
    s->got += n;
  }
  return true;
}

// THE line loop, under every entry point that splits lines.  From byte
// *pos (a line's first) and line number *line of the file: a line ends at
// its '\n' or at the end of the file (a trailing fragment without a
// newline is a line — the Q1 fix); the lines numbered in [start, end)
// (either < 0 = unbounded) are kept, up to max_rows of them.  A kept line
// goes to its row of out[max_rows][width]: ONE trailing '\r' stripped
// (CRLF), then cut to `width`, the rest of the row zeroed — so a '\r' is
// dropped only where it is the line's true last byte and lies inside the
// row; at the cut of an over-long line it is data.  With out == nullptr
// the kept lines are only counted.  Stops at a line boundary — max_rows
// kept, line `end` reached, or the file's end — and leaves both cursors
// there.  Returns the lines kept, or -1 on a read error.
long scan_lines(Source* s, long* pos, long* line, unsigned char* out,
                long max_rows, long width, long start, long end) {
  if (width >= kBuffer) return -1;
  long p = *pos, n = *line, row = 0;
  while (end < 0 || n < end) {
    const bool want = n >= start;
    if (want && row >= max_rows) break;
    if (p < s->base || p >= s->base + s->got) {
      if (s->at_eof && p >= s->base) break;  // the file's end
      if (!fill(s, p)) return -1;
      if (s->got == 0) break;
    }
    const unsigned char* at = s->buf + (p - s->base);
    long left = s->base + s->got - p;
    const void* lf = std::memchr(at, '\n', static_cast<size_t>(left));
    if (!lf && !s->at_eof && p > s->base) {
      // Cut by the buffer's end: once more, from the line's first byte.
      if (!fill(s, p)) return -1;
      at = s->buf;
      left = s->got;
      lf = std::memchr(at, '\n', static_cast<size_t>(left));
    }
    // The line's bytes in the buffer: all of them, or — of a line longer
    // than the buffer — more than a row keeps.
    long len = lf ? static_cast<const unsigned char*>(lf) - at : left;
    p += lf ? len + 1 : len;
    if (want) {
      if (out) {
        if (len > 0 && at[len - 1] == '\r') --len;
        if (len > width) len = width;
        unsigned char* dst = out + row * width;
        std::memcpy(dst, at, static_cast<size_t>(len));
        std::memset(dst + len, 0, static_cast<size_t>(width - len));
      }
      ++row;
    }
    ++n;
    while (!lf && !s->at_eof) {  // the rest of such a line streams past
      if (!fill(s, p)) return -1;
      lf = std::memchr(s->buf, '\n', static_cast<size_t>(s->got));
      p += lf ? static_cast<const unsigned char*>(lf) - s->buf + 1 : s->got;
    }
  }
  *pos = p;
  *line = n;
  return row;
}

}  // namespace

extern "C" {

// Number of lines in the file ('\n'-separated; a trailing fragment without
// a newline counts — the Q1 fix).  Returns -1 on I/O error.
long ingest_count_lines(const char* path) {
  Source s;
  if (!open_source(path, &s)) return -1;
  long pos = 0, line = 0;
  const long lines = scan_lines(&s, &pos, &line, nullptr, LONG_MAX, 0, -1, -1);
  close_source(s);
  return lines;
}

// Load lines [line_start, line_end) into out[max_lines][width], NUL-padded,
// '\r' stripped at line end, content truncated to width; every byte of the
// rows it returns is written, so `out` needs no zeroing first.  Negative
// start/end mean "whole file" (reference CLI default, main.cu:369-374).
// Returns rows written, or -1 on I/O error.
long ingest_load_rows(const char* path, unsigned char* out, long max_lines,
                      long width, long line_start, long line_end) {
  Source s;
  if (!open_source(path, &s)) return -1;
  long pos = 0, line = 0;
  const long rows = scan_lines(&s, &pos, &line, out, max_lines, width,
                               line_start, line_end);
  close_source(s);
  return rows;
}

// The streaming reader's file: opened by ingest_open, scanned a window at
// a time by ingest_window, closed by ingest_close.  The file is never
// materialized: one fixed 1 MB read buffer regardless of file or line
// length, which is what lets the 1GB+ north-star corpus (BASELINE.json)
// run in bounded RSS.  nullptr where the path is no regular file that
// opens (open_source).
void* ingest_open(const char* path) {
  Source s;
  if (!open_source(path, &s)) return nullptr;
  return new Source(s);
}

void ingest_close(void* handle) {
  Source* s = static_cast<Source*>(handle);
  close_source(*s);
  delete s;
}

// One window: resume at byte *inout_offset / line *inout_line, fill
// out[max_lines][width] (scan_lines' rows), honoring the [line_start,
// line_end) slice.  Advances the two cursors to the exact resume point
// (always a line boundary) and returns rows written — 0 means EOF or
// slice end, -1 a read error.  The rows past a short window's last line
// are zeroed: every byte of `out` is written by every call.
long ingest_window(void* handle, long* inout_offset, long* inout_line,
                   unsigned char* out, long max_lines, long width,
                   long line_start, long line_end) {
  const long rows =
      scan_lines(static_cast<Source*>(handle), inout_offset, inout_line, out,
                 max_lines, width, line_start, line_end);
  if (rows >= 0)
    std::memset(out + rows * width, 0,
                static_cast<size_t>(max_lines - rows) * width);
  return rows;
}

// Single-pass streaming caps measure: max token bytes + max tokens/line
// over the WIDTH-TRUNCATED view of each line in [line_start, line_end) —
// the same measurement io/loader.measure_caps_rows makes over staged row
// blocks (a token is a maximal run of non-delimiter bytes within the
// first `width` bytes; bytes past the truncation point are invisible, so
// a run caps there and later tokens on the line don't exist).  The
// delimiter set is PASSED IN (config.FULL_DELIMITERS) — a hardcoded copy
// here would drift from the device tokenizer and let --auto-caps
// under-size emits_per_line.  '\r' needs no special case: the windowed
// loader strips a trailing CR, but CR is in the delimiter set so a
// stripped-vs-kept CR closes the same token either way.  Floors are
// (1, 1) like the Python sites.  Returns 0, or -1 on I/O error.
long ingest_measure_caps(const char* path, long width, long line_start,
                         long line_end, const unsigned char* delims,
                         long n_delims, long* out_max_tok,
                         long* out_max_per_line) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return -1;
  bool lut[256] = {false};
  for (long i = 0; i < n_delims; ++i) lut[delims[i]] = true;
  lut[static_cast<unsigned char>('\n')] = true;  // line terminator anyway

  const long B = 1 << 20;
  unsigned char* buf = static_cast<unsigned char*>(std::malloc(B));
  if (!buf) {
    std::fclose(f);
    return -1;
  }
  const long start = line_start < 0 ? 0 : line_start;
  const long end = line_end;  // < 0 = unbounded
  long line = 0, pos = 0, run = 0, toks = 0;
  long max_tok = 1, max_per_line = 1;
  bool in_line = false;
  bool done = false;

  // Close the current token run / line, folding into the maxima.
  auto close_run = [&]() {
    if (run > max_tok) max_tok = run;
    run = 0;
  };
  auto close_line = [&]() {
    close_run();
    if (toks > max_per_line) max_per_line = toks;
    ++line;
    pos = 0;
    toks = 0;
    in_line = false;
  };

  while (!done) {
    long got = static_cast<long>(std::fread(buf, 1, B, f));
    if (got <= 0) {
      // A mid-file read ERROR must not return caps measured from a
      // prefix — silently undersized caps would drop real emits.
      if (std::ferror(f)) {
        std::free(buf);
        std::fclose(f);
        return -1;
      }
      break;  // clean EOF
    }
    for (long i = 0; i < got; ++i) {
      if (end >= 0 && line >= end) {
        done = true;
        break;
      }
      const unsigned char c = buf[i];
      if (c == '\n') {
        close_line();
        continue;
      }
      in_line = true;
      const bool want = line >= start;
      if (want && pos < width) {
        if (lut[c]) {
          close_run();
        } else {
          if (run == 0) ++toks;
          ++run;
        }
      }
      ++pos;
    }
  }
  if (in_line && !done) close_line();  // trailing fragment (Q1 semantics)
  std::free(buf);
  std::fclose(f);
  *out_max_tok = max_tok;
  *out_max_per_line = max_per_line;
  return 0;
}

// Streaming "key\tvalue" TSV parser — the native fast path for the
// reduce stage's intermediate loads (python analog: io/serde.read_tsv;
// reference analog: loadIntermediateFile, main.cu:66-103).  Semantics
// must match serde.read_tsv EXACTLY (parity-tested):
//   * split each line at the FIRST tab,
//   * strip trailing ' ' from the key (the reference writes "key \t", Q5)
//     — at the key's true end only, not at the width-truncation point,
//   * keys NUL-pad / truncate to key_width,
//   * values parse as base-10 ints with surrounding whitespace tolerated
//     (python int()); malformed values and empty keys skip the row,
//   * blank lines skip; '\r' before '\n' is stripped.
// Bounded memory: one fixed 1MB read buffer; per-line state carries only
// the first key_width key bytes and a small value buffer.
// Call with out_keys == NULL to COUNT parseable rows (pass 1), then with
// buffers sized [count, key_width] / [count] to fill (pass 2).
// Returns rows parsed/filled, or -1 on I/O error.
long ingest_read_tsv(const char* path, unsigned char* out_keys,
                     int* out_values, long max_rows, long key_width) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return -1;
  const long B = 1 << 20;
  unsigned char* buf = static_cast<unsigned char*>(std::malloc(B));
  if (!buf) {
    std::fclose(f);
    return -1;
  }
  const bool counting = out_keys == nullptr;
  long rows = 0;
  bool range_error = false;  // a value outside int32: hard error (-2)

  // Per-line state.  VMAX bounds a VALUE field; longer fields are
  // malformed rows in BOTH parsers (the strict grammar below).
  const int VMAX = 63;
  unsigned char keybuf[256];  // key prefix (key_width <= 256 enforced)
  unsigned char valbuf[VMAX];
  long klen = 0;        // total key bytes seen
  long last_ns = -1;    // index of last non-space key byte
  int vlen = 0;
  long pending_cr = 0;  // run of '\r' that may be the CRLF terminator
  bool in_value = false;
  bool val_too_long = false;
  if (key_width > 256) {
    std::free(buf);
    std::fclose(f);
    return -1;
  }

  auto isws = [](unsigned char c) {
    return c == ' ' || c == '\t' || c == '\r';
  };

  auto finish_line = [&]() {
    long eff = last_ns + 1;  // key length after trailing-space strip
    bool ok = eff > 0 && in_value && !val_too_long;
    long long value = 0;
    if (ok) {
      // The STRICT value grammar both parsers implement:
      //   [ws]* [+-]? [0-9]+ [ws]*      (ws = ' ' '\t' '\r')
      // Anything else (letters, NULs, underscores, second tabs) skips
      // the row; a syntactically valid value outside int32 is a HARD
      // error for the whole file (silent wrap would corrupt counts).
      int j = 0;
      while (j < vlen && isws(valbuf[j])) ++j;
      long long sign = 1;
      if (j < vlen && (valbuf[j] == '+' || valbuf[j] == '-')) {
        sign = valbuf[j] == '-' ? -1 : 1;
        ++j;
      }
      const int digits_start = j;
      while (j < vlen && valbuf[j] >= '0' && valbuf[j] <= '9') {
        if (value < (1LL << 40))  // keep accumulating until clearly over
          value = value * 10 + (valbuf[j] - '0');
        ++j;
      }
      if (j == digits_start) ok = false;  // no digits
      while (j < vlen && isws(valbuf[j])) ++j;
      if (j != vlen) ok = false;  // trailing junk (incl. NUL bytes)
      value *= sign;
      if (ok && (value > 2147483647LL || value < -2147483648LL))
        range_error = true;
    }
    if (ok && !range_error) {
      if (!counting && rows < max_rows) {
        long keep = eff < key_width ? eff : key_width;
        std::memset(out_keys + rows * key_width, 0,
                    static_cast<size_t>(key_width));
        std::memcpy(out_keys + rows * key_width, keybuf,
                    static_cast<size_t>(keep));
        out_values[rows] = static_cast<int>(value);
        ++rows;
      } else if (counting) {
        ++rows;
      }
    }
    klen = 0;
    last_ns = -1;
    vlen = 0;
    pending_cr = 0;
    in_value = false;
    val_too_long = false;
  };

  for (;;) {
    long got = static_cast<long>(std::fread(buf, 1, B, f));
    if (got <= 0) break;
    for (long i = 0; i < got && !range_error; ++i) {
      const unsigned char c = buf[i];
      if (c == '\n') {
        finish_line();
      } else if (!in_value) {
        if (c == '\t') {
          in_value = true;
        } else {
          if (c != ' ') last_ns = klen;  // only ' ' strips from key tails (Q5)
          if (klen < key_width) keybuf[klen] = c;
          ++klen;
        }
      } else {
        // Trailing '\r' runs are the line terminator, not value bytes
        // (the Python path rstrips them from the LINE before its length
        // check); only '\r's later followed by a non-'\r' byte are value
        // content and count toward the field budget.
        if (c == '\r') {
          ++pending_cr;
        } else {
          while (pending_cr > 0 && vlen < VMAX) {
            valbuf[vlen++] = '\r';
            --pending_cr;
          }
          if (pending_cr > 0) val_too_long = true;
          pending_cr = 0;
          if (vlen < VMAX) valbuf[vlen++] = c;
          else val_too_long = true;
        }
      }
    }
    if (range_error) break;
  }
  const bool io_error = std::ferror(f) != 0;
  if (!range_error && !io_error && (klen > 0 || in_value))
    finish_line();  // trailing line without '\n'
  std::free(buf);
  std::fclose(f);
  if (io_error) return -1;       // mid-file read error, NOT a short file
  if (range_error) return -2;    // int32 overflow in a value
  return rows;
}

// A CLEAN SNAP-style edge list -> int32 src[] / dst[] in one walk: the
// native spelling of plan/compile.py:_edges_clean, which stays as the
// fallback where no toolchain is and as the oracle the tests hold this
// to (tests/test_pagerank_cli.py).  Clean is EXACTLY what that accepts:
// '#' lines at the head only; then a line an edge — one or more digits,
// ONE TAB or SPACE, one or more digits, ONE LF (the last line may lack
// it).  A sign, a third field, a blank line, a CR, a '#' after the
// first edge, comments only, an empty body: -1, "not clean", and the
// caller's line loop words the error.  A number of more than 18 digits
// is not clean either, so no arithmetic here can wrap.  A value past
// int32 is NOT judged here: it is narrowed into its slot as written and
// *out_top carries the largest id in 64 bits, which the caller checks
// before it trusts an array (_check_top_id's PlanError).  ``cap`` is
// the room in src / dst (the file's LF count + 1 always holds it).
// Returns the edges written (>= 1), or -1.
long ingest_parse_edges(const unsigned char* data, long n, int* src,
                        int* dst, long cap, long long* out_top) {
  const unsigned char* p = data;
  const unsigned char* const end = data + n;
  while (p < end && *p == '#') {
    const void* lf = std::memchr(p, '\n', static_cast<size_t>(end - p));
    if (!lf) return -1;  // comments only
    p = static_cast<const unsigned char*>(lf) + 1;
  }
  long rows = 0;
  long long top = 0;
  // One field: 1..18 digits at p, advanced past them; -1 otherwise.
  auto field = [&]() -> long long {
    const unsigned char* const start = p;
    long long v = 0;
    unsigned d;
    while (p < end && (d = static_cast<unsigned>(*p) - '0') < 10u) {
      v = v * 10 + d;
      ++p;
    }
    return (p == start || p - start > 18) ? -1 : v;
  };
  while (p < end) {
    const long long a = field();
    if (a < 0 || p == end || (*p != '\t' && *p != ' ')) return -1;
    ++p;
    const long long b = field();
    if (b < 0 || rows == cap) return -1;
    if (p < end && *p++ != '\n') return -1;
    src[rows] = static_cast<int>(a);
    dst[rows] = static_cast<int>(b);
    ++rows;
    if (a > top) top = a;
    if (b > top) top = b;
  }
  if (rows == 0) return -1;  // an empty body
  *out_top = top;
  return rows;
}

// '\n' bytes in data[0, n): what sizes ingest_parse_edges' outputs.
// Summed 255 bytes at a time into ONE byte, which the compiler keeps in
// byte lanes (a fourth of the time of a long summed a byte at a time).
long ingest_count_lf(const unsigned char* data, long n) {
  long lf = 0;
  for (long i = 0; i < n;) {
    const long stop = n - i < 255 ? n : i + 255;
    unsigned char part = 0;
    for (; i < stop; ++i) part += data[i] == '\n';
    lf += part;
  }
  return lf;
}

}  // extern "C"
