// fastenc — native single-pass AdmissionReview JSON → feature-tensor encoder.
//
// The TPU serving pipeline's host-side bottleneck is encoding (SURVEY.md §7.4
// hard-part #1): walking the request JSON and scattering leaves into the
// policy-derived feature arrays (ops/codec.py). This is the native
// implementation of exactly that codec: a minimal JSON parser fused with the
// extraction trie, writing numeric/bool/presence features straight into the
// caller's numpy buffers. ID/pred strings resolve against a MIRROR of the
// Python intern table kept in the encoder handle (string bytes -> id and
// predicate bits, published by Python after it interned them); only strings
// the mirror has not seen go back to Python, as records over an arena.
//
// The batch call also does the LAUNCH's host half when asked (write_wire):
// while it still holds every row and not the GIL it ORs the rows into the
// liveness words and writes the one wire buffer of the wire form the launch
// handed in, byte for byte what evaluation/environment.py _live_words and
// _WireForm.wire make in numpy — so a warm launch makes no numpy call over
// the batch, and hands the interpreter away for none (PERF.md, PR 37).
//
// Semantics mirror ops/codec.py bit for bit:
//   * dtype mismatches are "missing" (mask stays 0): ID wants a JSON string;
//     F32 wants a number (bool excluded); I32 wants a syntactic integer
//     (bool and floats excluded); BOOL wants true/false.
//   * presence marks non-null leaves; null is absent.
//   * a '*' axis over an object iterates {"__key__", "__value__"} wrappers
//     in SORTED key order (codec.star_elements).
//   * axis overflow aborts the encode with the offending array id (the
//     caller raises SchemaOverflow and falls back to a wider bucket or the
//     host oracle).
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this environment).
// The entire encode runs without touching Python objects, so callers may
// release the GIL and encode batches on parallel threads.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <algorithm>
#include <atomic>
#include <memory>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

// ----------------------------------------------------------------- schema --

enum Kind : int32_t { KIND_VALUE = 0, KIND_PRESENT = 1, KIND_PRED = 2 };
enum DType : int32_t { DT_ID = 0, DT_F32 = 1, DT_BOOL = 2, DT_I32 = 3 };

struct Terminal {
  int32_t array_id;   // index into the caller's buffer table
  int32_t kind;       // Kind
  int32_t dtype;      // DType (KIND_VALUE only)
  int32_t mask_id;    // mask buffer index (KIND_VALUE only, else -1)
  int32_t pred_id;    // string-pred id (KIND_PRED only, else -1)
};

struct Node {
  std::unordered_map<std::string, std::unique_ptr<Node>> children;
  std::unique_ptr<Node> star;
  std::vector<Terminal> terminals;
  int32_t axis_cap = 0;     // cap of the star axis rooted here
  int32_t overflow_id = -1; // representative array id for overflow errors
};

struct ArrayInfo {
  int32_t ndim;        // 0..2 element axes
  int32_t caps[2];     // axis capacities
  int32_t elsize;      // bytes per element in the caller buffer
  int64_t row_stride;  // batch-mode row stride in BYTES: the width of the
                       // packed batch buffer the array is a column block of
  int64_t offset;      // batch-mode byte offset of that block within a row
};

// ----------------------------------------------------------------- mirror --
// What the Python intern table (utils/interning.py) said about the strings
// this encoder has seen: bytes -> id, and the bit of each of this encoder's
// predicates. The table stays the only source of ids and bits; Python
// publishes an entry (fastenc_learn) only after table.intern returned, so
// the table's publish-last rule holds here by construction.
//
// Many threads encode on one handle at once, GIL released, and must not wait
// on each other or on a publisher: the slots are a fixed open-addressed
// array of pointers, never resized and never cleared, so a reader is
// wait-free (acquire loads along a probe run) and a publisher, under a mutex
// only publishers take, fills an entry completely before one release store
// makes it visible. Bounded by construction: past kMaxEntries or kMaxBytes
// nothing more is published and a miss keeps the record path. Exact either
// way.

struct MirrorEntry {
  uint64_t hash;
  int32_t id;
  uint32_t len;
  // then len key bytes, then one byte per predicate of the encoder
  const char* key() const { return (const char*)(this + 1); }
  const uint8_t* bits() const { return (const uint8_t*)key() + len; }
};

struct Mirror {
  static constexpr size_t kSlots = 1 << 16;       // power of two
  static constexpr int64_t kMaxEntries = 1 << 15;  // slots at most half full
  static constexpr int64_t kMaxBytes = 4 << 20;    // entries, keys and bits
  static constexpr size_t kChunk = 1 << 16;

  std::unique_ptr<std::atomic<const MirrorEntry*>[]> slots{
      new std::atomic<const MirrorEntry*>[kSlots]()};
  int32_t n_preds = 0;
  std::atomic<int64_t> entries{0};
  // publishers only
  bool full = false;  // a cap refused an entry
  std::mutex publish_mu;
  std::vector<std::unique_ptr<char[]>> chunks;
  size_t chunk_used = kChunk;
  int64_t bytes = 0;

  static uint64_t hash_of(const char* s, size_t n) {
    uint64_t h = 1469598103934665603ull;  // FNV-1a
    for (size_t i = 0; i < n; i++) h = (h ^ (unsigned char)s[i]) * 1099511628211ull;
    return h;
  }

  const MirrorEntry* find(const char* s, size_t n, uint64_t h) const {
    for (size_t i = (size_t)h & (kSlots - 1);; i = (i + 1) & (kSlots - 1)) {
      const MirrorEntry* e = slots[i].load(std::memory_order_acquire);
      if (!e) return nullptr;
      if (e->hash == h && e->len == n && memcmp(e->key(), s, n) == 0) return e;
    }
  }

  // Caller holds publish_mu.
  void publish(const char* s, size_t n, int32_t id, const uint8_t* bits) {
    uint64_t h = hash_of(s, n);
    if (find(s, n, h)) return;
    size_t need = (sizeof(MirrorEntry) + n + (size_t)n_preds + 7) & ~(size_t)7;
    if (need > kChunk) return;  // a key too long to keep misses every time
    if (entries.load(std::memory_order_relaxed) >= kMaxEntries ||
        bytes + (int64_t)need > kMaxBytes) {
      full = true;
      return;
    }
    if (need > kChunk - chunk_used) {
      chunks.emplace_back(new char[kChunk]);
      chunk_used = 0;
    }
    char* at = chunks.back().get() + chunk_used;
    chunk_used += need;
    MirrorEntry* e = (MirrorEntry*)at;
    e->hash = h;
    e->id = id;
    e->len = (uint32_t)n;
    memcpy(at + sizeof(MirrorEntry), s, n);
    if (n_preds) memcpy(at + sizeof(MirrorEntry) + n, bits, (size_t)n_preds);
    bytes += (int64_t)need;
    size_t i = (size_t)h & (kSlots - 1);
    while (slots[i].load(std::memory_order_relaxed)) i = (i + 1) & (kSlots - 1);
    slots[i].store(e, std::memory_order_release);
    entries.fetch_add(1, std::memory_order_relaxed);
  }
};

struct Schema {
  Node root;
  std::vector<ArrayInfo> arrays;
  Mirror mirror;
};

// ------------------------------------------------------ schema JSON parse --
// The schema description itself arrives as JSON (built once at boot by
// ops/fastenc.py); we reuse the same parser.

struct Parser;

// ------------------------------------------------------------ JSON parser --

struct Parser {
  const char* p;
  const char* end;
  bool ok = true;

  explicit Parser(const char* data, size_t n) : p(data), end(data + n) {}

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r')) p++;
  }
  bool lit(const char* s, size_t n) {
    if ((size_t)(end - p) < n || memcmp(p, s, n) != 0) return false;
    p += n;
    return true;
  }
  // Parse a JSON string (assumes *p == '"'); appends decoded bytes to out.
  bool str(std::string& out) {
    if (p >= end || *p != '"') return false;
    p++;
    while (p < end) {
      unsigned char c = (unsigned char)*p;
      if (c == '"') { p++; return true; }
      if (c == '\\') {
        p++;
        if (p >= end) return false;
        char e = *p++;
        switch (e) {
          case '"': out.push_back('"'); break;
          case '\\': out.push_back('\\'); break;
          case '/': out.push_back('/'); break;
          case 'b': out.push_back('\b'); break;
          case 'f': out.push_back('\f'); break;
          case 'n': out.push_back('\n'); break;
          case 'r': out.push_back('\r'); break;
          case 't': out.push_back('\t'); break;
          case 'u': {
            if (end - p < 4) return false;
            unsigned int cp = 0;
            for (int i = 0; i < 4; i++) {
              char h = p[i];
              cp <<= 4;
              if (h >= '0' && h <= '9') cp |= h - '0';
              else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
              else return false;
            }
            p += 4;
            // surrogate pair
            if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 && p[0] == '\\' &&
                p[1] == 'u') {
              unsigned int lo = 0;
              bool okp = true;
              for (int i = 0; i < 4; i++) {
                char h = p[2 + i];
                lo <<= 4;
                if (h >= '0' && h <= '9') lo |= h - '0';
                else if (h >= 'a' && h <= 'f') lo |= h - 'a' + 10;
                else if (h >= 'A' && h <= 'F') lo |= h - 'A' + 10;
                else { okp = false; break; }
              }
              if (okp && lo >= 0xDC00 && lo <= 0xDFFF) {
                p += 6;
                cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
              }
            }
            // UTF-8 encode
            if (cp < 0x80) out.push_back((char)cp);
            else if (cp < 0x800) {
              out.push_back((char)(0xC0 | (cp >> 6)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            } else if (cp < 0x10000) {
              out.push_back((char)(0xE0 | (cp >> 12)));
              out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            } else {
              out.push_back((char)(0xF0 | (cp >> 18)));
              out.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
              out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
              out.push_back((char)(0x80 | (cp & 0x3F)));
            }
            break;
          }
          default: return false;
        }
      } else {
        out.push_back((char)c);
        p++;
      }
    }
    return false;
  }
  bool skip_string() {
    if (p >= end || *p != '"') return false;
    p++;
    while (p < end) {
      if (*p == '\\') { p += 2; continue; }
      if (*p == '"') { p++; return true; }
      p++;
    }
    return false;
  }
  bool skip_value() {
    ws();
    if (p >= end) return false;
    switch (*p) {
      case '"': return skip_string();
      case '{': {
        p++;
        ws();
        if (p < end && *p == '}') { p++; return true; }
        while (p < end) {
          ws();
          if (!skip_string()) return false;
          ws();
          if (p >= end || *p != ':') return false;
          p++;
          if (!skip_value()) return false;
          ws();
          if (p < end && *p == ',') { p++; continue; }
          if (p < end && *p == '}') { p++; return true; }
          return false;
        }
        return false;
      }
      case '[': {
        p++;
        ws();
        if (p < end && *p == ']') { p++; return true; }
        while (p < end) {
          if (!skip_value()) return false;
          ws();
          if (p < end && *p == ',') { p++; continue; }
          if (p < end && *p == ']') { p++; return true; }
          return false;
        }
        return false;
      }
      case 't': return lit("true", 4);
      case 'f': return lit("false", 5);
      case 'n': return lit("null", 4);
      default: {
        const char* start = p;
        while (p < end && (*p == '-' || *p == '+' || *p == '.' || *p == 'e' ||
                           *p == 'E' || (*p >= '0' && *p <= '9')))
          p++;
        return p > start;
      }
    }
  }
};

// ------------------------------------------------------------- the encode --

struct StringRecord {
  int32_t array_id;
  int32_t flat_offset;
  int32_t is_pred;   // 1 when this is a pred array cell
  int32_t pred_id;
  int32_t str_offset;
  int32_t str_len;
};

struct EncodeState {
  const Schema* schema;
  const Mirror* mirror = nullptr;  // null: every string leaf is a record
  uint8_t** buffers;       // array_id -> destination buffer
  std::string arena;       // collected ID/pred strings
  std::vector<StringRecord> records;
  int32_t error_array = -1;  // set on axis overflow / unencodable value
  bool unencodable = false;  // out-of-range numeric → oracle fallback
  std::string scratch;
};

inline int32_t flat_offset(const ArrayInfo& a, const int32_t* coords,
                           int depth) {
  // coords has `depth` entries; arrays may have fewer axes than the current
  // walk depth never happens (trie guarantees alignment).
  int32_t off = 0;
  for (int i = 0; i < a.ndim; i++) off = off * a.caps[i] + coords[i];
  return off;
}

// Values parsed at a leaf position.
enum LeafType { LEAF_NULL, LEAF_BOOL, LEAF_INT, LEAF_FLOAT, LEAF_STR, LEAF_CONTAINER };

struct Leaf {
  LeafType type = LEAF_NULL;
  bool b = false;
  double num = 0.0;
  int64_t inum = 0;
  const std::string* s = nullptr;  // points into EncodeState scratch/owned
};

// Out-of-range numerics must NOT silently truncate or read as missing —
// either would give a different verdict than the oracle (fail-open). They
// abort the row's encode; the host routes the request to the oracle
// (mirrors codec.UnencodableValue).
inline bool fits_i32(int64_t v) {
  return v >= INT32_MIN && v <= INT32_MAX;
}
inline bool fits_f32(double v) {
  return v == v && v <= 3.4028235677973366e38 && v >= -3.4028235677973366e38;
}

bool emit_terminals(EncodeState& st, const Node& node, const Leaf& leaf,
                    const int32_t* coords, int depth) {
  // what the mirror knows of this leaf's string, looked up once for all of
  // the node's terminals; null: not a string, no mirror, or never seen
  const MirrorEntry* known = nullptr;
  bool looked = false;
  auto resolve = [&]() {
    if (!looked && st.mirror) {
      known = st.mirror->find(leaf.s->data(), leaf.s->size(),
                              Mirror::hash_of(leaf.s->data(), leaf.s->size()));
    }
    looked = true;
    return known;
  };
  for (const Terminal& t : node.terminals) {
    const ArrayInfo& a = st.schema->arrays[(size_t)t.array_id];
    int32_t off = flat_offset(a, coords, depth);
    switch (t.kind) {
      case KIND_PRESENT:
        if (leaf.type != LEAF_NULL)
          st.buffers[t.array_id][off] = 1;
        break;
      case KIND_PRED:
        if (leaf.type == LEAF_STR) {
          if (resolve()) {
            st.buffers[t.array_id][off] = known->bits()[t.pred_id];
            break;
          }
          st.records.push_back({t.array_id, off, 1, t.pred_id,
                                (int32_t)st.arena.size(),
                                (int32_t)leaf.s->size()});
          st.arena.append(*leaf.s);
        }
        break;
      case KIND_VALUE: {
        uint8_t* buf = st.buffers[t.array_id];
        // mask_id == -1: the optimizer proved this column's validity
        // mask redundant (zero-fill folding) — no mask buffer exists
        uint8_t* mask = t.mask_id >= 0 ? st.buffers[t.mask_id] : nullptr;
        switch (t.dtype) {
          case DT_ID:
            if (leaf.type == LEAF_STR) {
              if (mask) mask[off] = 1;
              if (resolve()) {
                ((int32_t*)buf)[off] = known->id;
                break;
              }
              st.records.push_back({t.array_id, off, 0, -1,
                                    (int32_t)st.arena.size(),
                                    (int32_t)leaf.s->size()});
              st.arena.append(*leaf.s);
            }
            break;
          case DT_F32:
            if (leaf.type == LEAF_INT || leaf.type == LEAF_FLOAT) {
              double v =
                  leaf.type == LEAF_INT ? (double)leaf.inum : leaf.num;
              if (!fits_f32(v)) {
                st.unencodable = true;
                st.error_array = t.array_id;
                return false;
              }
              ((float*)buf)[off] = (float)v;
              if (mask) mask[off] = 1;
            }
            break;
          case DT_I32:
            if (leaf.type == LEAF_INT) {
              if (!fits_i32(leaf.inum)) {
                st.unencodable = true;
                st.error_array = t.array_id;
                return false;
              }
              ((int32_t*)buf)[off] = (int32_t)leaf.inum;
              if (mask) mask[off] = 1;
            }
            break;
          case DT_BOOL:
            if (leaf.type == LEAF_BOOL) {
              buf[off] = leaf.b ? 1 : 0;
              if (mask) mask[off] = 1;
            }
            break;
        }
        break;
      }
    }
  }
  return true;
}

// Forward decl.
bool walk(EncodeState& st, Parser& ps, const Node& node, int32_t* coords,
          int depth);

// Expand a '*' axis over the upcoming JSON value.
bool walk_star(EncodeState& st, Parser& ps, const Node& node, int32_t* coords,
               int depth) {
  ps.ws();
  if (ps.p >= ps.end) return false;
  const Node& star = *node.star;
  if (*ps.p == '[') {
    ps.p++;
    ps.ws();
    int32_t i = 0;
    if (ps.p < ps.end && *ps.p == ']') { ps.p++; return true; }
    while (ps.p < ps.end) {
      if (node.axis_cap && i >= node.axis_cap) {
        st.error_array = node.overflow_id;
        return false;
      }
      coords[depth] = i;
      if (!walk(st, ps, star, coords, depth + 1)) return false;
      i++;
      ps.ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      if (ps.p < ps.end && *ps.p == ']') { ps.p++; return true; }
      return false;
    }
    return false;
  }
  if (*ps.p == '{') {
    // Objects iterate {__key__, __value__} wrappers in SORTED key order; we
    // must buffer entries (key + raw value span) and re-walk them sorted.
    ps.p++;
    ps.ws();
    std::vector<std::pair<std::string, std::pair<const char*, const char*>>>
        entries;
    if (ps.p < ps.end && *ps.p == '}') {
      ps.p++;
    } else {
      while (ps.p < ps.end) {
        ps.ws();
        std::string key;
        if (!ps.str(key)) return false;
        ps.ws();
        if (ps.p >= ps.end || *ps.p != ':') return false;
        ps.p++;
        ps.ws();
        const char* vstart = ps.p;
        if (!ps.skip_value()) return false;
        entries.emplace_back(std::move(key), std::make_pair(vstart, ps.p));
        ps.ws();
        if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
        if (ps.p < ps.end && *ps.p == '}') { ps.p++; break; }
        return false;
      }
    }
    // Direct-key children coexist with the star expansion (e.g. both
    // metadata.labels[*] and metadata.labels.foo specs).
    if (!node.children.empty()) {
      for (auto& e : entries) {
        auto it = node.children.find(e.first);
        if (it != node.children.end()) {
          Parser sub(e.second.first,
                     (size_t)(e.second.second - e.second.first));
          if (!walk(st, sub, *it->second, coords, depth)) return false;
        }
      }
    }
    std::sort(entries.begin(), entries.end(),
              [](const auto& a, const auto& b) { return a.first < b.first; });
    if (node.axis_cap && (int32_t)entries.size() > node.axis_cap) {
      st.error_array = node.overflow_id;
      return false;
    }
    int32_t i = 0;
    for (auto& e : entries) {
      coords[depth] = i++;
      // The wrapper "element": terminals on the star node see a container.
      Leaf leaf;
      leaf.type = LEAF_CONTAINER;
      if (!emit_terminals(st, star, leaf, coords, depth + 1)) return false;
      // __key__ child
      auto kit = star.children.find("__key__");
      if (kit != star.children.end()) {
        Leaf kl;
        kl.type = LEAF_STR;
        kl.s = &e.first;
        if (!emit_terminals(st, *kit->second, kl, coords, depth + 1))
          return false;
        // __key__ has no deeper structure (it is a string)
      }
      // __value__ child: re-parse the buffered span
      auto vit = star.children.find("__value__");
      if (vit != star.children.end()) {
        Parser sub(e.second.first, (size_t)(e.second.second - e.second.first));
        if (!walk(st, sub, *vit->second, coords, depth + 1)) return false;
      }
      if (star.star) {
        // nested quantifier over the value (e.g. map value is an array):
        // matches codec semantics where the wrapper itself is the element
        // and deeper stars come from Elem sub-paths — wrapper dicts have no
        // direct star expansion.
      }
    }
    return true;
  }
  // Scalar under a star domain: not iterable — nothing to expand.
  return ps.skip_value();
}

bool walk(EncodeState& st, Parser& ps, const Node& node, int32_t* coords,
          int depth) {
  ps.ws();
  if (ps.p >= ps.end) return false;
  char c = *ps.p;

  // Leaf-typed values: emit terminals, no deeper traversal.
  if (c == '"') {
    st.scratch.clear();
    if (!ps.str(st.scratch)) return false;
    Leaf leaf;
    leaf.type = LEAF_STR;
    leaf.s = &st.scratch;
    if (!emit_terminals(st, node, leaf, coords, depth)) return false;
    return true;
  }
  if (c == 't' || c == 'f') {
    Leaf leaf;
    leaf.type = LEAF_BOOL;
    leaf.b = (c == 't');
    if (!(leaf.b ? ps.lit("true", 4) : ps.lit("false", 5))) return false;
    if (!emit_terminals(st, node, leaf, coords, depth)) return false;
    return true;
  }
  if (c == 'n') {
    if (!ps.lit("null", 4)) return false;
    Leaf leaf;  // LEAF_NULL
    if (!emit_terminals(st, node, leaf, coords, depth)) return false;
    return true;
  }
  if (c == '-' || (c >= '0' && c <= '9')) {
    const char* start = ps.p;
    bool is_float = false;
    while (ps.p < ps.end &&
           (*ps.p == '-' || *ps.p == '+' || *ps.p == '.' || *ps.p == 'e' ||
            *ps.p == 'E' || (*ps.p >= '0' && *ps.p <= '9'))) {
      if (*ps.p == '.' || *ps.p == 'e' || *ps.p == 'E') is_float = true;
      ps.p++;
    }
    std::string num(start, (size_t)(ps.p - start));
    Leaf leaf;
    if (is_float) {
      leaf.type = LEAF_FLOAT;
      leaf.num = strtod(num.c_str(), nullptr);
    } else {
      leaf.type = LEAF_INT;
      leaf.inum = strtoll(num.c_str(), nullptr, 10);
    }
    if (!emit_terminals(st, node, leaf, coords, depth)) return false;
    return true;
  }

  // Containers: presence terminals fire, then children / star.
  Leaf leaf;
  leaf.type = LEAF_CONTAINER;
  if (!emit_terminals(st, node, leaf, coords, depth)) return false;

  if (c == '{') {
    if (node.star) {
      // star over an object — handled by walk_star (it re-reads from p)
      return walk_star(st, ps, node, coords, depth);
    }
    ps.p++;
    ps.ws();
    if (ps.p < ps.end && *ps.p == '}') { ps.p++; return true; }
    while (ps.p < ps.end) {
      ps.ws();
      st.scratch.clear();
      std::string key;
      if (!ps.str(key)) return false;
      ps.ws();
      if (ps.p >= ps.end || *ps.p != ':') return false;
      ps.p++;
      auto it = node.children.find(key);
      if (it != node.children.end()) {
        if (!walk(st, ps, *it->second, coords, depth)) return false;
      } else {
        if (!ps.skip_value()) return false;
      }
      ps.ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      if (ps.p < ps.end && *ps.p == '}') { ps.p++; return true; }
      return false;
    }
    return false;
  }
  if (c == '[') {
    if (node.star) return walk_star(st, ps, node, coords, depth);
    return ps.skip_value();  // array where schema expects object: skip
  }
  return false;
}

// ------------------------------------------------- schema JSON description --

// Minimal DOM for the schema description (parsed once at boot; clarity over
// speed here).
struct SVal {
  enum T { OBJ, ARR, STR, NUM, BOOL_, NUL } t = NUL;
  std::unordered_map<std::string, std::unique_ptr<SVal>> obj;
  std::vector<std::unique_ptr<SVal>> arr;
  std::string s;
  double num = 0;
  bool b = false;
};

std::unique_ptr<SVal> parse_sval(Parser& ps) {
  ps.ws();
  auto v = std::make_unique<SVal>();
  if (ps.p >= ps.end) return nullptr;
  char c = *ps.p;
  if (c == '{') {
    v->t = SVal::OBJ;
    ps.p++;
    ps.ws();
    if (ps.p < ps.end && *ps.p == '}') { ps.p++; return v; }
    while (ps.p < ps.end) {
      ps.ws();
      std::string key;
      if (!ps.str(key)) return nullptr;
      ps.ws();
      if (ps.p >= ps.end || *ps.p != ':') return nullptr;
      ps.p++;
      auto child = parse_sval(ps);
      if (!child) return nullptr;
      v->obj.emplace(std::move(key), std::move(child));
      ps.ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      if (ps.p < ps.end && *ps.p == '}') { ps.p++; return v; }
      return nullptr;
    }
    return nullptr;
  }
  if (c == '[') {
    v->t = SVal::ARR;
    ps.p++;
    ps.ws();
    if (ps.p < ps.end && *ps.p == ']') { ps.p++; return v; }
    while (ps.p < ps.end) {
      auto child = parse_sval(ps);
      if (!child) return nullptr;
      v->arr.push_back(std::move(child));
      ps.ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      if (ps.p < ps.end && *ps.p == ']') { ps.p++; return v; }
      return nullptr;
    }
    return nullptr;
  }
  if (c == '"') {
    v->t = SVal::STR;
    if (!ps.str(v->s)) return nullptr;
    return v;
  }
  if (c == 't') { v->t = SVal::BOOL_; v->b = true; return ps.lit("true", 4) ? std::move(v) : nullptr; }
  if (c == 'f') { v->t = SVal::BOOL_; v->b = false; return ps.lit("false", 5) ? std::move(v) : nullptr; }
  if (c == 'n') { v->t = SVal::NUL; return ps.lit("null", 4) ? std::move(v) : nullptr; }
  v->t = SVal::NUM;
  const char* start = ps.p;
  while (ps.p < ps.end && (*ps.p == '-' || *ps.p == '+' || *ps.p == '.' ||
                           *ps.p == 'e' || *ps.p == 'E' ||
                           (*ps.p >= '0' && *ps.p <= '9')))
    ps.p++;
  if (ps.p == start) return nullptr;
  v->num = strtod(std::string(start, (size_t)(ps.p - start)).c_str(), nullptr);
  return v;
}

bool build_node(const SVal& desc, Node& out) {
  auto ti = desc.obj.find("terminals");
  if (ti != desc.obj.end()) {
    for (const auto& t : ti->second->arr) {
      Terminal term;
      term.array_id = (int32_t)t->obj.at("array")->num;
      term.kind = (int32_t)t->obj.at("kind")->num;
      term.dtype = (int32_t)t->obj.at("dtype")->num;
      term.mask_id = (int32_t)t->obj.at("mask")->num;
      term.pred_id = (int32_t)t->obj.at("pred")->num;
      out.terminals.push_back(term);
    }
  }
  auto ci = desc.obj.find("children");
  if (ci != desc.obj.end()) {
    for (const auto& kv : ci->second->obj) {
      auto child = std::make_unique<Node>();
      if (!build_node(*kv.second, *child)) return false;
      out.children.emplace(kv.first, std::move(child));
    }
  }
  auto si = desc.obj.find("star");
  if (si != desc.obj.end() && si->second->t == SVal::OBJ) {
    out.star = std::make_unique<Node>();
    if (!build_node(*si->second, *out.star)) return false;
    out.axis_cap = (int32_t)desc.obj.at("axis_cap")->num;
    out.overflow_id = (int32_t)desc.obj.at("overflow_id")->num;
  }
  return true;
}

// ------------------------------------------------------------- the wire --
// The launch's host half, done where the rows already are (the encode call,
// GIL released): the liveness words and the wire buffer of ONE wire form.
// The form is the launch's (evaluation/environment.py _WireForm) and comes
// in as plain arrays; nothing of its layout rule is written here beyond
// "gather these bytes, pack those":
//   live[w]     = OR over the batch's rows of the wide row's uint32 word w
//                 (environment._live_words)
//   wire[r][j]  = wide[r][take[j]]                      for j <  n_plain
//   the bytes take[n_plain..n_take) name are lanes: lane k is bit k % 8
//   of wire[r][n_plain + k / 8], set when its byte is non-zero (numpy's
//   packbits, bitorder little); the rest of the wire row, and every row
//   from n_rows up to wire_rows (the bucket's padding), is zero
//                 (environment._WireForm.wire, byte for byte).
// Mirrored field for field by ops/fastenc.py _WireRequest.
struct WireRequest {
  int64_t row_width;    // bytes of a wide row (a multiple of 4)
  const int64_t* take;  // the form's gather vector
  int64_t n_take;
  int64_t n_plain;      // leading entries of take copied as bytes
  int64_t wire_width;   // bytes of a wire row
  int64_t wire_rows;    // rows of the wire buffer (the batch bucket)
  uint8_t* wire;        // out: uint8[wire_rows, wire_width]
  uint32_t* live;       // out: uint32[row_width / 4]
};

// live[i] |= word i of one wide row (read through memcpy: the row is bytes).
inline void or_words(uint32_t* live, const uint8_t* row, int64_t words) {
  for (int64_t i = 0; i < words; i++) {
    uint32_t word;
    memcpy(&word, row + 4 * i, sizeof word);
    live[i] |= word;
  }
}

void write_wire(const WireRequest& w, const uint8_t* base, int64_t n_rows) {
  int64_t words = w.row_width / 4;
  memset(w.live, 0, (size_t)words * sizeof(uint32_t));
  memset(w.wire, 0, (size_t)(w.wire_rows * w.wire_width));
  int64_t n_lanes = w.n_take - w.n_plain;
  for (int64_t r = 0; r < n_rows; r++) {
    const uint8_t* row = base + r * w.row_width;
    or_words(w.live, row, words);
    uint8_t* out = w.wire + r * w.wire_width;
    for (int64_t j = 0; j < w.n_plain; j++) out[j] = row[w.take[j]];
    const int64_t* lane = w.take + w.n_plain;
    uint8_t* bits = out + w.n_plain;
    for (int64_t k = 0; k < n_lanes; k++)
      if (row[lane[k]]) bits[k >> 3] |= (uint8_t)(1u << (k & 7));
  }
}

}  // namespace

// ------------------------------------------------------------------ C ABI --

extern "C" {

// Build a schema from its JSON description. Returns an opaque handle or null.
void* fastenc_create(const char* schema_json, int64_t len) {
  Parser ps(schema_json, (size_t)len);
  auto desc = parse_sval(ps);
  if (!desc || desc->t != SVal::OBJ) return nullptr;
  auto schema = std::make_unique<Schema>();
  for (const auto& a : desc->obj.at("arrays")->arr) {
    ArrayInfo info{};
    const auto& caps = a->obj.at("caps")->arr;
    info.ndim = (int32_t)caps.size();
    for (size_t i = 0; i < caps.size() && i < 2; i++)
      info.caps[i] = (int32_t)caps[i]->num;
    info.elsize = (int32_t)a->obj.at("elsize")->num;
    info.row_stride = (int64_t)a->obj.at("row_stride")->num;
    info.offset = (int64_t)a->obj.at("offset")->num;
    schema->arrays.push_back(info);
  }
  schema->mirror.n_preds = (int32_t)desc->obj.at("n_preds")->num;
  if (!build_node(*desc->obj.at("trie"), schema->root)) return nullptr;
  return schema.release();
}

void fastenc_destroy(void* handle) { delete (Schema*)handle; }

// Encode one JSON document.
//   buffers    — array of pointers, one per schema array (pre-zeroed!)
//   arena      — output buffer for ID/pred string bytes
//   arena_cap  — its capacity
//   records    — output buffer of int32 sextuples (see StringRecord)
//   records_cap— its capacity IN RECORDS
// Returns: >=0 — number of string records written;
//          -1 — JSON parse error; -2 — arena/records overflow;
//          -(1000+array_id) — axis cap overflow on array_id.
int64_t fastenc_encode(void* handle, const char* json, int64_t len,
                       uint8_t** buffers, uint8_t* arena, int64_t arena_cap,
                       int32_t* records, int64_t records_cap) {
  Schema* schema = (Schema*)handle;
  EncodeState st;
  st.schema = schema;
  st.buffers = buffers;
  Parser ps(json, (size_t)len);
  int32_t coords[4] = {0, 0, 0, 0};
  bool ok = walk(st, ps, schema->root, coords, 0);
  if (!ok) {
    if (st.error_array >= 0) return -(1000 + (int64_t)st.error_array);
    return -1;
  }
  if ((int64_t)st.arena.size() > arena_cap ||
      (int64_t)st.records.size() > records_cap)
    return -2;
  memcpy(arena, st.arena.data(), st.arena.size());
  memcpy(records, st.records.data(),
         st.records.size() * sizeof(StringRecord));
  return (int64_t)st.records.size();
}

// Encode a BATCH of JSON documents directly into the packed batch buffer
// (codec.PackedLayout) — one call per dispatch, rows written in place, so
// the host never materializes per-request arrays or re-stacks them.
//   base       — the packed buffer (pre-zeroed); array i's column block of
//                row r starts at base + r * row_stride + offset, both from
//                the schema description
//   use_mirror — non-zero: strings the mirror knows are written as ids and
//                predicate bits here and leave no record; zero: every
//                string leaf is a record (a caller whose intern table is
//                not the one the mirror was published from)
//   row_status — per-row result: 0 ok, -1 parse error,
//                -(1000+array_id) axis overflow (those rows are re-tried
//                host-side on a wider bucket / the oracle)
//   records gain ABSOLUTE flat offsets (row * prod(caps) + local).
//   wire       — null, or the launch's host half asked for in the same call
//                (WireRequest above): written only when the batch left no
//                record, since a record means Python rewrites id columns
//                of the wide rows after this call returns
// Returns number of string records, or -2 on arena/records overflow.
int64_t fastenc_encode_batch(void* handle, const char** jsons,
                             const int64_t* lens, int64_t n_rows,
                             uint8_t* base, int32_t use_mirror,
                             uint8_t* arena, int64_t arena_cap,
                             int32_t* records, int64_t records_cap,
                             int32_t* row_status, const WireRequest* wire) {
  Schema* schema = (Schema*)handle;
  size_t n_arrays = schema->arrays.size();
  std::vector<int64_t> stride_elems(n_arrays), block_bytes(n_arrays);
  for (size_t i = 0; i < n_arrays; i++) {
    const ArrayInfo& a = schema->arrays[i];
    int64_t elems = 1;
    for (int d = 0; d < a.ndim; d++) elems *= a.caps[d];
    stride_elems[i] = elems;
    block_bytes[i] = elems * a.elsize;
  }
  std::vector<uint8_t*> row_buffers(n_arrays);
  std::string arena_acc;
  std::vector<StringRecord> records_acc;
  // Batch-level string dedup: what the mirror has not seen still repeats
  // within a batch, and the Python-side interning pass is O(#unique).
  std::unordered_map<std::string, int32_t> interned;
  EncodeState st;
  st.schema = schema;
  st.mirror = use_mirror ? &schema->mirror : nullptr;
  st.buffers = row_buffers.data();
  for (int64_t row = 0; row < n_rows; row++) {
    for (size_t i = 0; i < n_arrays; i++) {
      const ArrayInfo& a = schema->arrays[i];
      row_buffers[i] = base + row * a.row_stride + a.offset;
    }
    st.arena.clear();
    st.records.clear();
    st.error_array = -1;
    st.unencodable = false;
    Parser ps(jsons[row], (size_t)lens[row]);
    int32_t coords[4] = {0, 0, 0, 0};
    bool ok = walk(st, ps, schema->root, coords, 0);
    if (!ok) {
      row_status[row] =
          st.error_array >= 0 ? -(1000 + st.error_array) : -1;
      // wipe partial writes: the row still rides the batch dispatch and
      // must read as all-missing
      for (size_t i = 0; i < n_arrays; i++)
        memset(row_buffers[i], 0, (size_t)block_bytes[i]);
      continue;
    }
    row_status[row] = 0;
    for (StringRecord r : st.records) {
      std::string s(st.arena.data() + r.str_offset, (size_t)r.str_len);
      auto it = interned.find(s);
      int32_t off;
      if (it == interned.end()) {
        off = (int32_t)arena_acc.size();
        arena_acc.append(s);
        interned.emplace(std::move(s), off);
      } else {
        off = it->second;
      }
      r.str_offset = off;
      r.flat_offset += (int32_t)(row * stride_elems[(size_t)r.array_id]);
      records_acc.push_back(r);
    }
  }
  if ((int64_t)arena_acc.size() > arena_cap ||
      (int64_t)records_acc.size() > records_cap)
    return -2;
  // empty accumulators hand memcpy a null .data() — UB for a nonnull
  // parameter even at n=0 (no strings in the batch is a real case)
  if (!arena_acc.empty()) memcpy(arena, arena_acc.data(), arena_acc.size());
  if (!records_acc.empty())
    memcpy(records, records_acc.data(),
           records_acc.size() * sizeof(StringRecord));
  else if (wire != nullptr)
    write_wire(*wire, base, n_rows);
  return (int64_t)records_acc.size();
}

// What compacting a chunk does to the launch half its encode call wrote:
// rows pos[0..n) of the wire buffer go to the first n rows of dst, the
// rows after them zeroed, and live becomes the OR of the same rows of the
// WIDE buffer (the shipped rows' own liveness words, not the chunk's: the
// column set learns exactly what it learnt from a wide copy of them).
// 52 + 1,064 bytes a row read, small enough to run with the GIL held
// (ops/fastenc.py binds it through PyDLL): nothing is handed away.
void fastenc_take_rows(const uint8_t* wire, int64_t wire_width,
                       const uint8_t* wide, int64_t row_width,
                       const int64_t* pos, int64_t n, uint8_t* dst,
                       int64_t dst_rows, uint32_t* live) {
  int64_t words = row_width / 4;
  memset(live, 0, (size_t)words * sizeof(uint32_t));
  for (int64_t r = 0; r < n; r++) {
    memcpy(dst + r * wire_width, wire + pos[r] * wire_width,
           (size_t)wire_width);
    or_words(live, wide + pos[r] * row_width, words);
  }
  if (dst_rows > n)
    memset(dst + n * wire_width, 0, (size_t)((dst_rows - n) * wire_width));
}

// Publish n strings Python has interned: string i is the len[i] bytes at
// arena + offs[i], its table id ids[i], and bits + i * n_preds holds one
// byte for each of this encoder's predicates (the description's n_preds).
// A string already there is left as it is (its id and bits never change).
// Returns 1 once a cap has refused an entry — nothing more will be
// published, misses keep the record path — else 0.
int32_t fastenc_learn(void* handle, const char* arena, const int32_t* offs,
                      const int32_t* lens, const int32_t* ids,
                      const uint8_t* bits, int64_t n) {
  Mirror& m = ((Schema*)handle)->mirror;
  std::lock_guard<std::mutex> hold(m.publish_mu);
  for (int64_t i = 0; i < n; i++)
    m.publish(arena + offs[i], (size_t)lens[i], ids[i],
              bits + i * (int64_t)m.n_preds);
  return m.full ? 1 : 0;
}

int64_t fastenc_mirror_entries(void* handle) {
  return ((Schema*)handle)->mirror.entries.load(std::memory_order_relaxed);
}

}  // extern "C"
