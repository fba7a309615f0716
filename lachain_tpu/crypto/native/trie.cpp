// Native walks of the state trie (lachain_tpu/storage/trie.py): the bulk
// write walk of Trie.apply_many and the point read of Trie.get, over one
// node table a Trie (hash -> encoding, an LRU bounded in bytes).
//
// The walk keeps exactly the Python walker's rules (Trie._bulk,
// _build_subtree, _collapse_or_store) and its node format:
//   leaf      b"L" + key_hash(32) + u32be(len) + value
//   internal  b"I" + u16be(mask) + the present children's hashes, bit i = child i
// so roots and the set of stored nodes are bit-identical to it. The walk
// encodes each node it stores with a blank slot for a child stored in the
// same walk; then the nodes are keccak-hashed level by level bottom-up
// (children's hashes patched into the slots first), a large level on
// several threads. The stored
// nodes go back to Python as (prefixed key, encoding) pairs for the trie's
// write-back buffer, and into the table.
//
// A node the table lacks is fetched through the caller's loader (the
// trie's write-back buffer, then its KV store), so the table never answers
// for a node the trie's own store does not hold: what a walk stores enters
// the table only once the walk has succeeded, and nothing is evicted
// inside a call (the table is trimmed to its bound when a call ends).
//
// A process's tables share one byte budget (lt_trie_new's argument): each
// holds at most its even share, the budget over the live tables, so the
// 64 validators of one process hold what one validator alone would.
//
// One mutex a table: ctypes releases the GIL, and a node's pool may read
// the state on one thread while the block tail executes on another. The
// loader runs under the mutex; it must not call back into the same table.
#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

extern "C" void lt_keccak256(const uint8_t *in, size_t inlen, uint8_t out[32]);

namespace {

struct H32 {
  uint8_t b[32];
  bool operator==(const H32 &o) const { return std::memcmp(b, o.b, 32) == 0; }
  bool operator<(const H32 &o) const { return std::memcmp(b, o.b, 32) < 0; }
  bool empty() const {
    static const uint8_t zero[32] = {0};
    return std::memcmp(b, zero, 32) == 0;
  }
};

struct H32Hash {
  size_t operator()(const H32 &h) const {
    uint64_t x;
    std::memcpy(&x, h.b, 8);  // keccak output: already uniform
    return (size_t)x;
  }
};

struct Entry {
  std::string enc;
  Entry *prev = nullptr, *next = nullptr;
  const H32 *key = nullptr;
};

// what an entry costs beyond its encoding: the map node, the links, the
// string's header and the allocator's rounding
constexpr uint64_t kEntryOverhead = 128;
constexpr uint32_t kDeleted = 0xFFFFFFFFu;
// a thread of a hashing level gets at least this many bytes to hash
constexpr uint64_t kBytesPerThread = 64 << 10;

// (hash, buf, cap) -> encoding length (written to buf only if it fits),
// -1 no such node, -2 the loader failed (the caller holds the error)
typedef int64_t (*loader_fn)(const uint8_t *hash, uint8_t *buf, int64_t cap);

// the process's budget, and the tables that share it
std::atomic<uint64_t> g_budget{0};
std::atomic<uint64_t> g_tables{0};

struct Table {
  std::mutex mu;
  std::unordered_map<H32, Entry, H32Hash> map;
  Entry *head = nullptr, *tail = nullptr;  // head: most recently used
  uint64_t bytes = 0;
  uint64_t hits = 0, loads = 0;  // since the last lt_trie_counts
  std::vector<uint8_t> loadbuf = std::vector<uint8_t>(4096);  // the loader's

  void unlink(Entry *e) {
    if (e->prev) e->prev->next = e->next; else head = e->next;
    if (e->next) e->next->prev = e->prev; else tail = e->prev;
    e->prev = e->next = nullptr;
  }
  void push_front(Entry *e) {
    e->prev = nullptr;
    e->next = head;
    if (head) head->prev = e;
    head = e;
    if (!tail) tail = e;
  }
  void touch(Entry *e) {
    if (head == e) return;
    unlink(e);
    push_front(e);
  }
  Entry *insert(const H32 &h, const uint8_t *enc, size_t n) {
    auto it = map.find(h);
    if (it != map.end()) {
      touch(&it->second);
      return &it->second;
    }
    auto res = map.emplace(h, Entry());
    Entry *e = &res.first->second;
    e->enc.assign((const char *)enc, n);
    e->key = &res.first->first;
    push_front(e);
    bytes += n + kEntryOverhead;
    return e;
  }
  void erase(const H32 &h) {
    auto it = map.find(h);
    if (it == map.end()) return;
    unlink(&it->second);
    bytes -= it->second.enc.size() + kEntryOverhead;
    map.erase(it);
  }
  void trim() {
    uint64_t cap = g_budget.load() / std::max<uint64_t>(1, g_tables.load());
    while (bytes > cap && tail) erase(*tail->key);
  }
  void clear() {
    map.clear();
    head = tail = nullptr;
    bytes = 0;
  }
};

enum Status : int64_t {
  kOk = 0,
  kMissing = 1,  // a node the store does not hold (hash reported)
  kLoaderError = 2,
  kBadNode = 3,  // an encoding that is neither leaf nor internal node
};

struct Abort {
  Status status;
};

inline int nibble(const uint8_t *h, int depth) {
  uint8_t byte = h[depth >> 1];
  return (depth & 1) ? (byte & 0x0F) : (byte >> 4);
}

inline uint32_t be32(const uint8_t *p) {
  return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
         ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

// what every entry of the table has passed: a leaf or an internal node
// whose lengths stay inside its encoding
void check(const uint8_t *p, size_t n) {
  if (n >= 1 && p[0] == 'L') {
    if (n < 37 || (uint64_t)37 + be32(p + 33) > n) throw Abort{kBadNode};
    return;
  }
  if (n >= 3 && p[0] == 'I') {
    uint16_t mask = (uint16_t)((p[1] << 8) | p[2]);
    if ((size_t)3 + 32 * (size_t)__builtin_popcount(mask) > n)
      throw Abort{kBadNode};
    return;
  }
  throw Abort{kBadNode};
}

// run fn(lo, hi) over [0, n) in `threads` slices, one on this thread
template <class F>
void parallel(size_t n, int threads, F fn) {
  if ((size_t)threads > n) threads = (int)n;
  if (threads <= 1) {
    fn((size_t)0, n);
    return;
  }
  std::vector<std::thread> ts;
  ts.reserve(threads - 1);
  for (int k = 1; k < threads; k++)
    ts.emplace_back(fn, n * k / threads, n * (k + 1) / threads);
  fn((size_t)0, n / threads);
  for (auto &th : ts) th.join();
}

// a node reference in a walk: a hash, or the index of a node this walk
// stored and has not hashed yet
struct Ref {
  H32 h;
  int32_t idx;
  bool empty() const { return idx < 0 && h.empty(); }
};

inline Ref ref(const H32 &h) { return Ref{h, -1}; }

// one write of a batch: hashed key, value (kDeleted: a delete)
struct Op {
  H32 kh;
  const uint8_t *val;
  uint32_t len;
};

// a leaf to build: the hashed key and the value's bytes
struct Leaf {
  const uint8_t *kh;
  const uint8_t *val;
  uint32_t len;
};

// a stored node: its encoding at [off, off + len) of the walk's buffer,
// its level (0 for a node with no unhashed child), and its blank child
// slots at [patch, patch_end) of the patch list
struct Stored {
  uint64_t off;
  uint32_t len, level;
  uint64_t patch, patch_end;
};

struct Patch {
  uint64_t pos;  // where in the encodings buffer the child's hash goes
  int32_t child;
};

struct Walk {
  Table &t;
  loader_fn loader;
  H32 missing{};
  std::vector<uint8_t> encs;
  std::vector<Stored> nodes;
  std::vector<Patch> patches;
  std::vector<H32> hashes;  // by node index, once hashed

  Walk(Table &t_, loader_fn l) : t(t_), loader(l) {}

  // ---- node io ----
  const std::string &load(const H32 &h) {
    auto it = t.map.find(h);
    if (it != t.map.end()) {
      t.touch(&it->second);
      t.hits++;
      return it->second.enc;
    }
    std::vector<uint8_t> &buf = t.loadbuf;
    int64_t n = loader(h.b, buf.data(), (int64_t)buf.size());
    if (n > (int64_t)buf.size()) {
      buf.resize((size_t)n);
      n = loader(h.b, buf.data(), n);
    }
    if (n == -1) {
      missing = h;
      throw Abort{kMissing};
    }
    if (n < 0 || n > (int64_t)buf.size()) throw Abort{kLoaderError};
    t.loads++;
    check(buf.data(), (size_t)n);
    return t.insert(h, buf.data(), (size_t)n)->enc;
  }

  static void children(const std::string &enc, H32 out[16]) {
    const uint8_t *p = (const uint8_t *)enc.data();
    uint16_t mask = (uint16_t)((p[1] << 8) | p[2]);
    const uint8_t *c = p + 3;
    for (int i = 0; i < 16; i++) {
      if (mask & (1u << i)) {
        std::memcpy(out[i].b, c, 32);
        c += 32;
      } else {
        std::memset(out[i].b, 0, 32);
      }
    }
  }

  Ref store_leaf(const uint8_t *kh, const uint8_t *val, uint32_t len) {
    uint64_t off = encs.size();
    encs.resize(off + 37 + (size_t)len);
    uint8_t *p = encs.data() + off;
    p[0] = 'L';
    std::memcpy(p + 1, kh, 32);
    p[33] = (uint8_t)(len >> 24);
    p[34] = (uint8_t)(len >> 16);
    p[35] = (uint8_t)(len >> 8);
    p[36] = (uint8_t)len;
    if (len) std::memcpy(p + 37, val, len);
    nodes.push_back({off, 37 + len, 0, patches.size(), patches.size()});
    return Ref{H32{}, (int32_t)(nodes.size() - 1)};
  }

  Ref store_internal(const Ref kids[16]) {
    uint64_t off = encs.size();
    uint64_t patch = patches.size();
    uint16_t mask = 0;
    uint32_t level = 0;
    encs.resize(off + 3);
    for (int i = 0; i < 16; i++) {
      if (kids[i].empty()) continue;
      mask |= (uint16_t)(1u << i);
      uint64_t pos = encs.size();
      encs.insert(encs.end(), kids[i].h.b, kids[i].h.b + 32);
      if (kids[i].idx >= 0) {
        patches.push_back({pos, kids[i].idx});
        level = std::max(level, nodes[kids[i].idx].level + 1);
      }
    }
    uint8_t *p = encs.data() + off;
    p[0] = 'I';
    p[1] = (uint8_t)(mask >> 8);
    p[2] = (uint8_t)mask;
    nodes.push_back({off, (uint32_t)(encs.size() - off), level, patch,
                     patches.size()});
    return Ref{H32{}, (int32_t)(nodes.size() - 1)};
  }

  // ---- the walk: Trie._bulk, _build_subtree, _collapse_or_store ----
  Ref collapse_or_store(const Ref kids[16]) {
    int live = -1, count = 0;
    for (int i = 0; i < 16; i++) {
      if (!kids[i].empty()) {
        if (live < 0) live = i;
        count++;
      }
    }
    if (count == 0) return ref(H32{});
    if (count == 1) {
      const Ref &only = kids[live];
      if (only.idx >= 0) {
        const Stored &s = nodes[only.idx];
        if (encs[s.off] == 'L') {
          // the same leaf stored again, as the Python walker does; copied
          // out first, since storing grows the buffer it lies in
          std::vector<uint8_t> leaf(encs.begin() + s.off,
                                    encs.begin() + s.off + s.len);
          return store_leaf(leaf.data() + 1, leaf.data() + 37,
                            (uint32_t)leaf.size() - 37);
        }
      } else {
        const std::string &enc = load(only.h);
        const uint8_t *p = (const uint8_t *)enc.data();
        if (p[0] == 'L') return store_leaf(p + 1, p + 37, be32(p + 33));
      }
    }
    return store_internal(kids);
  }

  Ref build(const Leaf *leaves, size_t n, int depth) {
    if (n == 0) return ref(H32{});
    if (n == 1) return store_leaf(leaves[0].kh, leaves[0].val, leaves[0].len);
    if (depth >= 64) throw Abort{kBadNode};  // two leaves of one hashed key
    Ref kids[16];
    for (auto &k : kids) k = ref(H32{});
    size_t i = 0;
    while (i < n) {
      int nib = nibble(leaves[i].kh, depth);
      size_t j = i + 1;
      while (j < n && nibble(leaves[j].kh, depth) == nib) j++;
      kids[nib] = build(leaves + i, j - i, depth + 1);
      i = j;
    }
    return store_internal(kids);
  }

  Ref bulk(const H32 &nh, const Op *ops, size_t n, int depth) {
    if (n == 0) return ref(nh);
    if (nh.empty()) {
      std::vector<Leaf> leaves;
      leaves.reserve(n);
      for (size_t i = 0; i < n; i++)
        if (ops[i].len != kDeleted)
          leaves.push_back({ops[i].kh.b, ops[i].val, ops[i].len});
      return build(leaves.data(), leaves.size(), depth);
    }
    // a table entry stays put for the whole call: nothing is evicted
    // before it ends, and the map never moves its elements
    const std::string &enc = load(nh);
    const uint8_t *p = (const uint8_t *)enc.data();
    if (p[0] == 'L') {
      // the batch merged over the leaf: a write of its key replaces it
      Leaf old{p + 1, p + 37, be32(p + 33)};
      std::vector<Leaf> leaves;
      leaves.reserve(n + 1);
      bool placed = false;
      for (size_t i = 0; i < n; i++) {
        if (!placed) {
          int c = std::memcmp(ops[i].kh.b, old.kh, 32);
          if (c >= 0) {
            placed = true;
            if (c > 0) leaves.push_back(old);
          }
        }
        if (ops[i].len != kDeleted)
          leaves.push_back({ops[i].kh.b, ops[i].val, ops[i].len});
      }
      if (!placed) leaves.push_back(old);
      if (leaves.size() == 1 && std::memcmp(leaves[0].kh, old.kh, 32) == 0 &&
          leaves[0].len == old.len &&
          (old.len == 0 || std::memcmp(leaves[0].val, old.val, old.len) == 0))
        return ref(nh);  // no-op batch over this leaf
      return build(leaves.data(), leaves.size(), depth);
    }
    H32 old[16];
    children(enc, old);
    Ref kids[16];
    for (int k = 0; k < 16; k++) kids[k] = ref(old[k]);
    size_t i = 0;
    while (i < n) {
      int nib = nibble(ops[i].kh.b, depth);
      size_t j = i + 1;
      while (j < n && nibble(ops[j].kh.b, depth) == nib) j++;
      kids[nib] = bulk(old[nib], ops + i, j - i, depth + 1);
      i = j;
    }
    bool same = true;
    for (int k = 0; k < 16 && same; k++)
      same = kids[k].idx < 0 && kids[k].h == old[k];
    if (same) return ref(nh);  // absent-key deletes / same-value puts
    return collapse_or_store(kids);
  }

  // hash the stored nodes bottom-up: a level's children are all hashed
  // before it, so its blank slots can be filled and it can be hashed
  void hash_all(int threads) {
    hashes.resize(nodes.size());
    uint32_t top = 0;
    for (const Stored &s : nodes) top = std::max(top, s.level);
    std::vector<std::vector<int32_t>> levels(top + 1);
    std::vector<uint64_t> level_bytes(top + 1, 0);
    for (size_t i = 0; i < nodes.size(); i++) {
      levels[nodes[i].level].push_back((int32_t)i);
      level_bytes[nodes[i].level] += nodes[i].len;
    }
    for (uint32_t l = 0; l <= top; l++) {
      const std::vector<int32_t> &idx = levels[l];
      int want = (int)std::min<uint64_t>((uint64_t)threads,
                                         level_bytes[l] / kBytesPerThread + 1);
      parallel(idx.size(), want, [&](size_t lo, size_t hi) {
        for (size_t k = lo; k < hi; k++) {
          const Stored &s = nodes[idx[k]];
          for (uint64_t q = s.patch; q < s.patch_end; q++)
            std::memcpy(&encs[patches[q].pos], hashes[patches[q].child].b, 32);
          lt_keccak256(encs.data() + s.off, s.len, hashes[idx[k]].b);
        }
      });
    }
  }
};

struct ApplyResult {
  std::vector<uint8_t> keys, encs;
  std::vector<uint64_t> offs;
};

thread_local std::string tls_value;

}  // namespace

extern "C" {

// a table that shares budget_bytes with the process's other tables
void *lt_trie_new(uint64_t budget_bytes) {
  g_budget.store(budget_bytes);
  g_tables.fetch_add(1);
  return new Table();
}

void lt_trie_free(void *h) {
  delete (Table *)h;
  g_tables.fetch_sub(1);
}

void lt_trie_clear(void *h) {
  Table *t = (Table *)h;
  std::lock_guard<std::mutex> g(t->mu);
  t->clear();
}

// out[0] = table hits, out[1] = loads through the loader, since the last
// call; both reset
void lt_trie_counts(void *h, uint64_t out[2]) {
  Table *t = (Table *)h;
  std::lock_guard<std::mutex> g(t->mu);
  out[0] = t->hits;
  out[1] = t->loads;
  t->hits = t->loads = 0;
}

// out[0] = entries, out[1] = bytes charged against the bound, out[2] =
// the bound (the process's budget over its live tables)
void lt_trie_size(void *h, uint64_t out[3]) {
  Table *t = (Table *)h;
  std::lock_guard<std::mutex> g(t->mu);
  out[0] = t->map.size();
  out[1] = t->bytes;
  out[2] = g_budget.load() / std::max<uint64_t>(1, g_tables.load());
}

// adopt n nodes another trie stored: node i is hash keys[i*stride +
// stride-32 ..] (the write-back buffer's prefixed keys), encoding
// encs[offs[i], offs[i+1])
void lt_trie_insert(void *h, const uint8_t *keys, uint64_t stride,
                    const uint8_t *encs, const uint64_t *offs, uint64_t n) {
  Table *t = (Table *)h;
  std::lock_guard<std::mutex> g(t->mu);
  for (uint64_t i = 0; i < n; i++) {
    H32 k;
    std::memcpy(k.b, keys + i * stride + stride - 32, 32);
    const uint8_t *enc = encs + offs[i];
    size_t len = (size_t)(offs[i + 1] - offs[i]);
    try {
      check(enc, len);
    } catch (const Abort &) {
      continue;  // never served: a walk that needs it loads it
    }
    t->insert(k, enc, len);
  }
  t->trim();
}

// Apply n writes to the tree under root: key i is the next klens[i] bytes
// of keys, value i the next vlens[i] bytes of vals unless vlens[i] ==
// 0xFFFFFFFF (a delete); hashing on up to `threads` threads. -> a result
// handle (free with lt_trie_result_free); info = [status, stored nodes,
// keys' pointer (34 bytes a node: prefix + hash), encodings' pointer,
// offsets' pointer (stored + 1 entries), encodings' bytes]; root_out holds
// the new root, or on kMissing the missing node's hash. On any failure
// the table holds what it held, plus what the walk loaded.
void *lt_trie_apply(void *h, const uint8_t *root, const uint8_t *keys,
                    const uint32_t *klens, const uint8_t *vals,
                    const uint32_t *vlens, uint64_t n, uint32_t prefix,
                    int threads, loader_fn loader, uint8_t *root_out,
                    uint64_t info[6]) {
  Table *t = (Table *)h;
  std::vector<Op> ops(n);
  std::vector<uint64_t> koffs(n + 1, 0);
  const uint8_t *v = vals;
  for (uint64_t i = 0; i < n; i++) {
    koffs[i + 1] = koffs[i] + klens[i];
    ops[i].len = vlens[i];
    ops[i].val = v;
    if (vlens[i] != kDeleted) v += vlens[i];
  }
  int kthreads = (int)std::min<uint64_t>((uint64_t)threads, n / 4096 + 1);
  parallel(n, kthreads, [&](size_t lo, size_t hi) {
    for (size_t i = lo; i < hi; i++)
      lt_keccak256(keys + koffs[i], klens[i], ops[i].kh.b);
  });
  // one op a hashed key, the last write winning (a dict's rule)
  std::stable_sort(ops.begin(), ops.end(),
                   [](const Op &a, const Op &b) { return a.kh < b.kh; });
  size_t m = 0;
  for (size_t i = 0; i < ops.size(); i++) {
    if (m && ops[m - 1].kh == ops[i].kh)
      ops[m - 1] = ops[i];
    else
      ops[m++] = ops[i];
  }
  ops.resize(m);

  ApplyResult *res = new ApplyResult();
  res->offs.assign(1, 0);
  std::lock_guard<std::mutex> g(t->mu);
  Walk w(*t, loader);
  H32 r;
  std::memcpy(r.b, root, 32);
  int64_t status = kOk;
  try {
    Ref out = w.bulk(r, ops.data(), ops.size(), 0);
    w.hash_all(threads);
    const H32 &new_root = out.idx >= 0 ? w.hashes[out.idx] : out.h;
    std::memcpy(root_out, new_root.b, 32);
  } catch (const Abort &a) {
    status = a.status;
    if (status == kMissing) std::memcpy(root_out, w.missing.b, 32);
  }
  if (status == kOk) {
    size_t count = w.nodes.size();
    res->keys.resize(34 * count);
    res->offs.resize(count + 1);
    res->encs.reserve(w.encs.size());
    for (size_t i = 0; i < count; i++) {
      const Stored &s = w.nodes[i];
      uint8_t *k = res->keys.data() + 34 * i;
      k[0] = (uint8_t)(prefix >> 8);
      k[1] = (uint8_t)prefix;
      std::memcpy(k + 2, w.hashes[i].b, 32);
      const uint8_t *enc = w.encs.data() + s.off;
      res->encs.insert(res->encs.end(), enc, enc + s.len);
      res->offs[i + 1] = res->encs.size();
      t->insert(w.hashes[i], enc, s.len);
    }
  }
  t->trim();
  info[0] = (uint64_t)status;
  info[1] = (uint64_t)(res->offs.size() - 1);
  info[2] = (uint64_t)(uintptr_t)res->keys.data();
  info[3] = (uint64_t)(uintptr_t)res->encs.data();
  info[4] = (uint64_t)(uintptr_t)res->offs.data();
  info[5] = (uint64_t)res->encs.size();
  return res;
}

void lt_trie_result_free(void *r) { delete (ApplyResult *)r; }

// The value under key in the tree under root: -> its length (the bytes at
// info[0], valid on this thread until its next call), -1 absent; on
// failure -(1 + status), with the missing node's hash at info[0] for
// kMissing. info[1] = nodes this call loaded through the loader.
int64_t lt_trie_get(void *h, const uint8_t *root, const uint8_t *key,
                    uint64_t keylen, loader_fn loader, uint64_t info[2]) {
  Table *t = (Table *)h;
  H32 kh;
  lt_keccak256(key, keylen, kh.b);
  std::lock_guard<std::mutex> g(t->mu);
  Walk w(*t, loader);
  uint64_t loads0 = t->loads;
  int64_t ret = -1;
  try {
    H32 node;
    std::memcpy(node.b, root, 32);
    for (int depth = 0;; depth++) {
      const std::string &enc = w.load(node);
      const uint8_t *p = (const uint8_t *)enc.data();
      if (p[0] == 'L') {
        if (std::memcmp(p + 1, kh.b, 32) == 0) {
          uint32_t len = be32(p + 33);
          tls_value.assign((const char *)p + 37, len);
          ret = len;
        }
        break;
      }
      if (depth >= 64) throw Abort{kBadNode};
      uint16_t mask = (uint16_t)((p[1] << 8) | p[2]);
      int nib = nibble(kh.b, depth);
      if (!(mask & (1u << nib))) break;
      int before = __builtin_popcount(mask & ((1u << nib) - 1));
      std::memcpy(node.b, p + 3 + 32 * before, 32);
    }
  } catch (const Abort &a) {
    ret = -(1 + (int64_t)a.status);
    if (a.status == kMissing)
      tls_value.assign((const char *)w.missing.b, 32);
  }
  info[1] = t->loads - loads0;
  t->trim();
  info[0] = (uint64_t)(uintptr_t)tls_value.data();
  return ret;
}

}  // extern "C"
