// Baseline JFIF entropy encoder over device-produced JPEG coefficients.
//
// Native fast path for the Python reference in ../jfif.py — the two
// implement the same deterministic algorithm (ITU T.81 Annex K.2 optimal
// Huffman construction, canonical code assignment, 4:2:0 interleaved MCU
// scan, byte-stuffed bit packing) and must produce byte-identical streams;
// tests/test_jpeg.py asserts that equality.
//
// Replaces the serial half of the reference's CPU JPEG stage
// (LocalCompress.compressToStream, ImageRegionRequestHandler.java:580-582).
// The lossy half (DCT/quantization) runs on TPU (../ops/jpegenc.py).
//
// C ABI only (loaded via ctypes; no pybind11 in this image).
//
// A call allocates nothing: the symbol records, the block offsets and the
// output stream live in a Scratch the caller owns (jpeg_scratch_new),
// grown when a larger tile arrives and never zero-filled.  Per-call
// vectors of that size (6.7 MB of records at 1024^2, 26.7 MB at 2048^2)
// went to mmap / munmap, and several threads coding at once then
// serialised on the process's address-space lock.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <new>

namespace {

// ----------------------------------------------------------- tables

const int kBaseLuma[64] = {
    16, 11, 10, 16, 24, 40, 51, 61, 12, 12, 14, 19, 26, 58, 60, 55,
    14, 13, 16, 24, 40, 57, 69, 56, 14, 17, 22, 29, 51, 87, 80, 62,
    18, 22, 37, 56, 68, 109, 103, 77, 24, 35, 55, 64, 81, 104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101, 72, 92, 95, 98, 112, 100, 103, 99};

const int kBaseChroma[64] = {
    17, 18, 24, 47, 99, 99, 99, 99, 18, 21, 26, 66, 99, 99, 99, 99,
    24, 26, 56, 99, 99, 99, 99, 99, 47, 66, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99};

void quant_tables(int quality, uint8_t qy[64], uint8_t qc[64]) {
  quality = std::max(1, std::min(100, quality));
  int scale = quality < 50 ? 5000 / quality : 200 - 2 * quality;
  for (int i = 0; i < 64; i++) {
    int a = (kBaseLuma[i] * scale + 50) / 100;
    int b = (kBaseChroma[i] * scale + 50) / 100;
    qy[i] = static_cast<uint8_t>(std::max(1, std::min(255, a)));
    qc[i] = static_cast<uint8_t>(std::max(1, std::min(255, b)));
  }
}

// Zigzag: flat index into a row-major 8x8 block per zigzag position,
// generated the same way as ops/jpegenc.py zigzag_order().
struct ZigZag {
  int at[64];
  ZigZag() {
    struct RC { int r, c; };
    RC order[64];
    for (int r = 0; r < 8; r++)
      for (int c = 0; c < 8; c++) order[r * 8 + c] = {r, c};
    std::sort(order, order + 64, [](const RC& a, const RC& b) {
      int sa = a.r + a.c, sb = b.r + b.c;
      if (sa != sb) return sa < sb;
      int ka = (sa % 2 == 0) ? a.c : a.r;
      int kb = (sb % 2 == 0) ? b.c : b.r;
      return ka < kb;
    });
    for (int i = 0; i < 64; i++) at[i] = order[i].r * 8 + order[i].c;
  }
};

const int* zigzag_order() {
  static const ZigZag zig;  // built once, by whichever thread is first
  return zig.at;
}

// ----------------------------------------------------------- huffman K.2

struct HuffTable {
  int bits[33] = {0};       // bits[1..16] used after limiting
  uint8_t huffval[256];
  int n_huffval = 0;
  uint32_t code_of[256] = {0};
  int len_of[256] = {0};
};

void build_huffman(const int64_t freq_in[256], HuffTable* t) {
  int64_t freq[257];
  std::memcpy(freq, freq_in, sizeof(int64_t) * 256);
  freq[256] = 1;  // reserved: no real symbol gets the all-ones code
  int codesize[257] = {0};
  int others[257];
  std::fill(others, others + 257, -1);

  for (;;) {
    // v1: smallest nonzero frequency, ties -> largest symbol value.
    int v1 = -1, v2 = -1;
    int64_t f1 = INT64_MAX, f2 = INT64_MAX;
    for (int i = 0; i < 257; i++) {
      if (freq[i] <= 0) continue;
      if (freq[i] <= f1) { f1 = freq[i]; v1 = i; }
    }
    for (int i = 0; i < 257; i++) {
      if (freq[i] <= 0 || i == v1) continue;
      if (freq[i] <= f2) { f2 = freq[i]; v2 = i; }
    }
    if (v2 < 0) break;
    freq[v1] += freq[v2];
    freq[v2] = 0;
    codesize[v1]++;
    while (others[v1] != -1) { v1 = others[v1]; codesize[v1]++; }
    others[v1] = v2;
    codesize[v2]++;
    while (others[v2] != -1) { v2 = others[v2]; codesize[v2]++; }
  }

  for (int i = 0; i < 257; i++)
    if (codesize[i] > 0) t->bits[codesize[i]]++;

  // ADJUST_BITS (figure K.3).
  int i = 32;
  while (i > 16) {
    if (t->bits[i] > 0) {
      int j = i - 2;
      while (t->bits[j] == 0) j--;
      t->bits[i] -= 2;
      t->bits[i - 1] += 1;
      t->bits[j + 1] += 2;
      t->bits[j] -= 1;
    } else {
      i--;
    }
  }
  i = 16;
  while (t->bits[i] == 0) i--;
  t->bits[i] -= 1;

  // HUFFVAL ordered by (code length, symbol value); canonical codes.
  for (int len = 1; len <= 32; len++)
    for (int s = 0; s < 256; s++)
      if (codesize[s] == len)
        t->huffval[t->n_huffval++] = static_cast<uint8_t>(s);

  uint32_t code = 0;
  int k = 0;
  for (int len = 1; len <= 16; len++) {
    for (int n = 0; n < t->bits[len]; n++) {
      uint8_t sym = t->huffval[k++];
      t->code_of[sym] = code;
      t->len_of[sym] = len;
      code++;
    }
    code <<= 1;
  }
}

// ----------------------------------------------------------- scratch

// Per-block symbol record: DC category/value + AC (symbol, value) list.
struct BlockSyms {
  int dc_sym;
  int dc_val;
  int dc_abs;  // absolute DC (the next block's predictor)
  // packed (symbol << 16) | (value & 0xFFFF); at most 63 ACs + EOB.
  int n_ac;
  uint32_t ac[64];
};

// Scratch growths of the whole process: the contract check that the
// scratch is kept (a count, not a speed) reads it.
std::atomic<long long> g_scratch_growths{0};

// What a coding call works in.  Owned by the caller, one call at a time;
// nothing in it survives a call but its capacity.
struct Scratch {
  BlockSyms* syms = nullptr;  // luma | Cb | Cr records of one tile
  size_t syms_cap = 0;        // in records
  int* start = nullptr;       // per-block entry offsets (sparse)
  size_t start_cap = 0;
  uint8_t* out = nullptr;     // the JFIF streams of one call, end to end
  size_t out_n = 0, out_cap = 0;

  // The records and offsets are written before they are read: a grown
  // block is a fresh one, its old contents dropped.
  template <typename T>
  static bool fresh(T** p, size_t* cap, size_t need) {
    if (need <= *cap) return true;
    std::free(*p);
    *p = static_cast<T*>(std::malloc(need * sizeof(T)));
    *cap = *p ? need : 0;
    g_scratch_growths++;
    return *p != nullptr;
  }
  // The stream keeps what it holds (earlier rows of a run).
  void reserve_out(size_t need) {
    if (need <= out_cap) return;
    size_t cap = std::max(need, out_cap * 2);
    uint8_t* p = static_cast<uint8_t*>(std::realloc(out, cap));
    if (!p) throw std::bad_alloc();
    out = p;
    out_cap = cap;
    g_scratch_growths++;
  }
  inline void push_back(uint8_t b) {
    if (out_n == out_cap) reserve_out(out_n + 1);
    out[out_n++] = b;
  }
  void append(const uint8_t* p, size_t n) {
    reserve_out(out_n + n);
    std::memcpy(out + out_n, p, n);
    out_n += n;
  }
  ~Scratch() { std::free(syms); std::free(start); std::free(out); }
};

// ----------------------------------------------------------- bit writer

struct BitWriter {
  Scratch& out;
  uint64_t acc = 0;
  int nbits = 0;
  explicit BitWriter(Scratch& o) : out(o) {}
  inline void put(uint32_t code, int length) {
    if (length == 0) return;
    acc = (acc << length) | (code & ((1ull << length) - 1));
    nbits += length;
    while (nbits >= 8) {
      nbits -= 8;
      uint8_t byte = static_cast<uint8_t>((acc >> nbits) & 0xFF);
      out.push_back(byte);
      if (byte == 0xFF) out.push_back(0x00);
    }
    acc &= (1ull << nbits) - 1;
  }
  void flush() {
    if (nbits) {
      int pad = 8 - nbits;
      put((1u << pad) - 1, pad);
    }
  }
};

inline int category(int v) {
  unsigned a = v < 0 ? -v : v;
  int s = 0;
  while (a) { s++; a >>= 1; }
  return s;
}

inline uint32_t amplitude_bits(int v, int size) {
  return static_cast<uint32_t>(v >= 0 ? v : v + (1 << size) - 1);
}

// Sparse variant: the block is given as `n` (position, value) entries with
// strictly ascending zigzag positions — exactly what the device's
// sparse_pack emits.  Positions absent from the list are zero.  Returns
// false on a malformed buffer (n > 64, positions not strictly ascending
// or > 63) rather than trusting wire data into fixed-size arrays.
// Read the 18-bit entry at index j of the packed stream (MSB-first at
// bit 18j): 6-bit zigzag position << 12 | 12-bit two's-complement value.
// The 32-bit window of the stream's last entries may reach past `end`
// (the row is read where it lies, a prefix fetch especially): bytes
// beyond it read as zero.
static inline uint32_t read_entry18(const uint8_t* stream,
                                    const uint8_t* end, long long j) {
  long long bit = j * 18;
  const uint8_t* p = stream + (bit >> 3);
  int shift = static_cast<int>(bit & 7);
  uint32_t window;
  if (p + 4 <= end) {
    window = (static_cast<uint32_t>(p[0]) << 24)
           | (static_cast<uint32_t>(p[1]) << 16)
           | (static_cast<uint32_t>(p[2]) << 8)
           | static_cast<uint32_t>(p[3]);
  } else {
    window = 0;
    for (int k = 0; k < 4 && p + k < end; k++)
      window |= static_cast<uint32_t>(p[k]) << (24 - 8 * k);
  }
  return (window >> (32 - 18 - shift)) & 0x3FFFF;
}

static inline int entry_val(uint32_t field) {
  int v = static_cast<int>(field & 0xFFF);
  return v >= 2048 ? v - 4096 : v;
}

bool block_symbols_sparse(const uint8_t* stream, const uint8_t* end,
                          long long first, int n, int pred, BlockSyms* bs,
                          int64_t* dc_freq, int64_t* ac_freq) {
  // Entries [first, first+n) of the 18-bit packed stream.
  if (n < 0 || n > 64) return false;
  int k = 0;
  int dc = 0;
  if (n > 0) {
    uint32_t f = read_entry18(stream, end, first);
    if ((f >> 12) == 0) { dc = entry_val(f); k = 1; }
  }
  int dc_diff = dc - pred;
  bs->dc_sym = category(dc_diff);
  bs->dc_val = dc_diff;
  bs->dc_abs = dc;
  dc_freq[bs->dc_sym]++;
  bs->n_ac = 0;
  int last = 0;
  for (; k < n; k++) {
    uint32_t f = read_entry18(stream, end, first + k);
    int p = static_cast<int>(f >> 12);
    if (p <= last || p > 63) return false;
    int run = p - last - 1;
    last = p;
    while (run >= 16) {
      bs->ac[bs->n_ac++] = (0xF0u << 16);
      ac_freq[0xF0]++;
      run -= 16;
    }
    int v = entry_val(f);
    uint32_t sym = (static_cast<uint32_t>(run) << 4) | category(v);
    bs->ac[bs->n_ac++] = (sym << 16) | (static_cast<uint32_t>(v) & 0xFFFF);
    ac_freq[sym]++;
  }
  if (last != 63) {
    bs->ac[bs->n_ac++] = 0;  // EOB
    ac_freq[0x00]++;
  }
  return true;
}

void block_symbols(const int16_t* block, int pred, BlockSyms* bs,
                   int64_t* dc_freq, int64_t* ac_freq) {
  int dc_diff = static_cast<int>(block[0]) - pred;
  bs->dc_sym = category(dc_diff);
  bs->dc_val = dc_diff;
  bs->dc_abs = block[0];
  dc_freq[bs->dc_sym]++;
  bs->n_ac = 0;
  int run = 0;
  int last = 0;  // index of last nonzero (1-based into block), 0 = none yet
  for (int i = 1; i < 64; i++) {
    if (block[i] == 0) continue;
    run = i - last - 1;
    last = i;
    while (run >= 16) {
      bs->ac[bs->n_ac++] = (0xF0u << 16);
      ac_freq[0xF0]++;
      run -= 16;
    }
    int v = block[i];
    uint32_t sym = (static_cast<uint32_t>(run) << 4) | category(v);
    bs->ac[bs->n_ac++] = (sym << 16) | (static_cast<uint32_t>(v) & 0xFFFF);
    ac_freq[sym]++;
  }
  if (last != 63) {
    bs->ac[bs->n_ac++] = 0;  // EOB
    ac_freq[0x00]++;
  }
}

void emit_marker(Scratch& out, uint8_t tag, const uint8_t* payload,
                 size_t len) {
  size_t n = len + 2;
  const uint8_t head[4] = {0xFF, tag, static_cast<uint8_t>(n >> 8),
                           static_cast<uint8_t>(n & 0xFF)};
  out.append(head, 4);
  out.append(payload, len);
}

// Shared framing + Huffman build + bit-packing over collected symbols:
// one JFIF stream appended to the scratch's output.
void emit_jfif(Scratch& out, const BlockSyms* ysyms,
               const BlockSyms* cbsyms, const BlockSyms* crsyms,
               const int64_t y_dcf[256], const int64_t y_acf[256],
               const int64_t c_dcf[256], const int64_t c_acf[256],
               int width, int height, int quality) {
  int h16 = (height + 15) / 16, w16 = (width + 15) / 16;
  int n_mcu = h16 * w16;

  uint8_t qy[64], qc[64];
  quant_tables(quality, qy, qc);
  const int* zig = zigzag_order();

  HuffTable dc0, ac0, dc1, ac1;
  build_huffman(y_dcf, &dc0);
  build_huffman(y_acf, &ac0);
  build_huffman(c_dcf, &dc1);
  build_huffman(c_acf, &ac1);

  out.reserve_out(out.out_n + static_cast<size_t>(n_mcu) * 96 + 1024);
  out.push_back(0xFF); out.push_back(0xD8);  // SOI
  const uint8_t app0[] = {'J', 'F', 'I', 'F', 0, 1, 1, 0, 0, 1, 0, 1, 0, 0};
  emit_marker(out, 0xE0, app0, sizeof(app0));
  {
    uint8_t p[65];
    p[0] = 0;
    for (int i = 0; i < 64; i++) p[1 + i] = qy[zig[i]];
    emit_marker(out, 0xDB, p, 65);
    p[0] = 1;
    for (int i = 0; i < 64; i++) p[1 + i] = qc[zig[i]];
    emit_marker(out, 0xDB, p, 65);
  }
  const uint8_t sof0[] = {8,
      static_cast<uint8_t>(height >> 8), static_cast<uint8_t>(height & 0xFF),
      static_cast<uint8_t>(width >> 8), static_cast<uint8_t>(width & 0xFF),
      3, 1, 0x22, 0, 2, 0x11, 1, 3, 0x11, 1};
  emit_marker(out, 0xC0, sof0, sizeof(sof0));
  const HuffTable* dht_tables[4] = {&dc0, &ac0, &dc1, &ac1};
  const int dht_cls[4] = {0, 1, 0, 1};
  const int dht_id[4] = {0, 0, 1, 1};
  for (int k = 0; k < 4; k++) {
    const HuffTable* t = dht_tables[k];
    uint8_t p[17 + 256];
    p[0] = static_cast<uint8_t>((dht_cls[k] << 4) | dht_id[k]);
    for (int i = 1; i <= 16; i++) p[i] = static_cast<uint8_t>(t->bits[i]);
    std::memcpy(p + 17, t->huffval, t->n_huffval);
    emit_marker(out, 0xC4, p, 17 + t->n_huffval);
  }
  const uint8_t sos[] = {3, 1, 0x00, 2, 0x11, 3, 0x11, 0, 63, 0};
  emit_marker(out, 0xDA, sos, sizeof(sos));

  BitWriter bw(out);
  auto put_block = [&bw](const BlockSyms& bs, const HuffTable& dc,
                         const HuffTable& ac) {
    bw.put(dc.code_of[bs.dc_sym], dc.len_of[bs.dc_sym]);
    if (bs.dc_sym) bw.put(amplitude_bits(bs.dc_val, bs.dc_sym), bs.dc_sym);
    for (int i = 0; i < bs.n_ac; i++) {
      uint32_t sym = bs.ac[i] >> 16;
      int v = static_cast<int16_t>(bs.ac[i] & 0xFFFF);
      bw.put(ac.code_of[sym], ac.len_of[sym]);
      int size = sym & 0x0F;
      if (size) bw.put(amplitude_bits(v, size), size);
    }
  };
  int yi = 0;
  for (int m = 0; m < n_mcu; m++) {
    for (int k = 0; k < 4; k++) put_block(ysyms[yi++], dc0, ac0);
    put_block(cbsyms[m], dc1, ac1);
    put_block(crsyms[m], dc1, ac1);
  }
  bw.flush();
  out.push_back(0xFF); out.push_back(0xD9);  // EOI
}

// One tile from its sparse wire row; see jpeg_encode_sparse_run.
long long encode_sparse_row(Scratch& s, const uint8_t* buf, size_t buf_len,
                            int width, int height, int quality, int cap) {
  if (!buf || width <= 0 || height <= 0 || cap <= 0) return -1;
  int h16 = (height + 15) / 16, w16 = (width + 15) / 16;
  int n_mcu = h16 * w16;
  int nb_y = n_mcu * 4, nb_c = n_mcu;
  int nb = nb_y + 2 * nb_c;
  if (buf_len < 4 + static_cast<size_t>(nb)) return -1;

  int32_t total;
  std::memcpy(&total, buf, 4);
  if (total > cap) return -2;
  if (total < 0 ||
      buf_len < 4 + static_cast<size_t>(nb) +
                    (static_cast<size_t>(total) * 18 + 7) / 8) return -1;
  const uint8_t* counts = buf + 4;
  const uint8_t* stream = buf + 4 + nb;
  const uint8_t* end = buf + buf_len;

  if (!Scratch::fresh(&s.start, &s.start_cap, static_cast<size_t>(nb) + 1) ||
      !Scratch::fresh(&s.syms, &s.syms_cap, static_cast<size_t>(nb)))
    return -1;
  // Per-block entry offsets (prefix sum of counts, flat block order).
  int* start = s.start;
  start[0] = 0;
  for (int b = 0; b < nb; b++) start[b + 1] = start[b] + counts[b];
  if (start[nb] != total) return -1;

  BlockSyms* ysyms = s.syms;
  BlockSyms* cbsyms = ysyms + nb_y;
  BlockSyms* crsyms = cbsyms + nb_c;
  int64_t y_dcf[256] = {0}, y_acf[256] = {0};
  int64_t c_dcf[256] = {0}, c_acf[256] = {0};
  int ypred = 0, cbpred = 0, crpred = 0;
  int yw = w16 * 2;
  int yi = 0;
  for (int my = 0; my < h16; my++) {
    for (int mx = 0; mx < w16; mx++) {
      const int yidx[4] = {
          (2 * my) * yw + 2 * mx, (2 * my) * yw + 2 * mx + 1,
          (2 * my + 1) * yw + 2 * mx, (2 * my + 1) * yw + 2 * mx + 1};
      for (int k = 0; k < 4; k++) {
        int b = yidx[k];
        if (!block_symbols_sparse(stream, end, start[b],
                                  start[b + 1] - start[b], ypred,
                                  &ysyms[yi++], y_dcf, y_acf))
          return -1;
        ypred = ysyms[yi - 1].dc_abs;
      }
      int ci = my * w16 + mx;
      int b = nb_y + ci;
      if (!block_symbols_sparse(stream, end, start[b],
                                start[b + 1] - start[b], cbpred,
                                &cbsyms[ci], c_dcf, c_acf))
        return -1;
      cbpred = cbsyms[ci].dc_abs;
      b = nb_y + nb_c + ci;
      if (!block_symbols_sparse(stream, end, start[b],
                                start[b + 1] - start[b], crpred,
                                &crsyms[ci], c_dcf, c_acf))
        return -1;
      crpred = crsyms[ci].dc_abs;
    }
  }
  size_t at = s.out_n;
  emit_jfif(s, ysyms, cbsyms, crsyms, y_dcf, y_acf, c_dcf, c_acf,
            width, height, quality);
  return static_cast<long long>(s.out_n - at);
}

}  // namespace

extern "C" {

// A coder's scratch: what jpeg_encode and jpeg_encode_sparse_run work
// in, kept by the caller from one call to the next (one call at a time
// a scratch).  It holds what the largest tile it has coded needed:
// 272 bytes a block (6.7 MB at 1024^2, 26.7 MB at 2048^2) and the
// streams of the largest run.
void* jpeg_scratch_new() { return new (std::nothrow) Scratch(); }

void jpeg_scratch_free(void* scratch) {
  delete static_cast<Scratch*>(scratch);
}

// Bytes the scratch retains.
size_t jpeg_scratch_bytes(const void* scratch) {
  const Scratch* s = static_cast<const Scratch*>(scratch);
  return s->syms_cap * sizeof(BlockSyms) + s->start_cap * sizeof(int) +
         s->out_cap;
}

// Times any scratch of the process took a larger block than it had.
long long jpeg_scratch_growths() { return g_scratch_growths.load(); }

// Encode one image's zigzagged raster-order coefficient blocks to JFIF.
// y: (h16*2)*(w16*2) blocks of 64 int16; cb, cr: h16*w16 blocks each,
// where h16 = ceil(height/16), w16 = ceil(width/16).  Returns the stream
// (in the scratch, valid until its next call) and its length in *n, or
// null on invalid arguments.
const uint8_t* jpeg_encode(void* scratch, const int16_t* y,
                           const int16_t* cb, const int16_t* cr,
                           int width, int height, int quality,
                           long long* n) {
  if (!scratch || width <= 0 || height <= 0 || !y || !cb || !cr || !n)
    return nullptr;
  Scratch& s = *static_cast<Scratch*>(scratch);
  int h16 = (height + 15) / 16, w16 = (width + 15) / 16;
  int n_mcu = h16 * w16;
  int yw = w16 * 2;

  // Pass 1: symbols + frequencies in MCU scan order.
  if (!Scratch::fresh(&s.syms, &s.syms_cap,
                      static_cast<size_t>(n_mcu) * 6))
    return nullptr;
  BlockSyms* ysyms = s.syms;
  BlockSyms* cbsyms = ysyms + static_cast<size_t>(n_mcu) * 4;
  BlockSyms* crsyms = cbsyms + n_mcu;
  int64_t y_dcf[256] = {0}, y_acf[256] = {0};
  int64_t c_dcf[256] = {0}, c_acf[256] = {0};
  int ypred = 0, cbpred = 0, crpred = 0;
  int yi = 0;
  for (int my = 0; my < h16; my++) {
    for (int mx = 0; mx < w16; mx++) {
      const int yidx[4] = {
          (2 * my) * yw + 2 * mx, (2 * my) * yw + 2 * mx + 1,
          (2 * my + 1) * yw + 2 * mx, (2 * my + 1) * yw + 2 * mx + 1};
      for (int k = 0; k < 4; k++) {
        const int16_t* blk = y + static_cast<size_t>(yidx[k]) * 64;
        block_symbols(blk, ypred, &ysyms[yi++], y_dcf, y_acf);
        ypred = blk[0];
      }
      int ci = my * w16 + mx;
      const int16_t* cbb = cb + static_cast<size_t>(ci) * 64;
      const int16_t* crb = cr + static_cast<size_t>(ci) * 64;
      block_symbols(cbb, cbpred, &cbsyms[ci], c_dcf, c_acf);
      cbpred = cbb[0];
      block_symbols(crb, crpred, &crsyms[ci], c_dcf, c_acf);
      crpred = crb[0];
    }
  }
  s.out_n = 0;
  try {
    emit_jfif(s, ysyms, cbsyms, crsyms, y_dcf, y_acf, c_dcf, c_acf,
              width, height, quality);
  } catch (const std::bad_alloc&) {
    return nullptr;
  }
  *n = static_cast<long long>(s.out_n);
  return s.out;
}

// Encode a run of `n` images straight from the device's sparse wire
// rows (ops/jpegenc.py sparse_pack layout: [total i32 LE | counts
// u8[nb] | packed 18-bit (pos << 12 | val) entries], blocks ordered
// luma raster, Cb raster, Cr raster), one after the other, in one call:
// the caller's thread gives the interpreter up once a run, not once a
// tile.  Row i is bufs[i][0:lens[i]], read where it lies; it may be a
// prefix fetch: any length >= 4 + nb + ceil(18*total/8) decodes.
// rc[i] is the length of row i's stream, which starts off[i] bytes into
// the returned block (the scratch's, valid until its next call); or -1
// on invalid arguments or a malformed row, -2 if the row overflowed
// `cap` (entries dropped; the caller must take the dense path).  A row
// that fails leaves the others as they are.
const uint8_t* jpeg_encode_sparse_run(void* scratch, int n,
                                      const uint8_t* const* bufs,
                                      const size_t* lens,
                                      const int* widths, const int* heights,
                                      int quality, int cap,
                                      long long* rc, size_t* off) {
  if (!scratch || n < 0 || !bufs || !lens || !widths || !heights || !rc ||
      !off)
    return nullptr;
  Scratch& s = *static_cast<Scratch*>(scratch);
  s.out_n = 0;
  for (int i = 0; i < n; i++) {
    off[i] = s.out_n;
    try {
      rc[i] = encode_sparse_row(s, bufs[i], lens[i], widths[i], heights[i],
                                quality, cap);
    } catch (const std::bad_alloc&) {
      rc[i] = -1;
    }
    if (rc[i] < 0) s.out_n = off[i];
  }
  return s.out;
}

}  // extern "C"
