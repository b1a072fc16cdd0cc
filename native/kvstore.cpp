// Embedded ordered key-value store (C++ host runtime).
//
// Role: the native storage engine behind lighthouse_tpu.store — the
// equivalent of the reference's LevelDB dependency
// (/root/reference/beacon_node/store/src/leveldb_store.rs, leveldb-sys C++).
// Design: append-only log + in-memory ordered index (std::map), crash-safe
// via CRC-checked records and truncate-on-torn-tail recovery, compaction by
// rewrite. Exposed to Python over a C ABI via ctypes (no pybind11 in image).
//
// Build: see native/build.sh (g++ -O2 -shared -fPIC).

#include <unistd.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <vector>

namespace {

uint32_t crc32(const uint8_t* data, size_t n, uint32_t crc = 0) {
  static uint32_t table[256];
  static bool init = false;
  if (!init) {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = c & 1 ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      table[i] = c;
    }
    init = true;
  }
  crc = ~crc;
  for (size_t i = 0; i < n; i++) crc = table[(crc ^ data[i]) & 0xFF] ^ (crc >> 8);
  return ~crc;
}

constexpr uint32_t kDeleteMarker = 0xFFFFFFFFu;
// klen sentinel framing a whole write batch as ONE CRC'd record: the payload
// holds [u32 count] then per-op [u32 klen][u32 vlen|kDeleteMarker][key][val].
// Replay applies a batch only when its CRC checks out, so a crash mid-batch
// (torn tail) drops the entire batch — never a prefix of it.
constexpr uint32_t kBatchMarker = 0xFFFFFFFEu;

struct Record {
  uint64_t offset;  // offset of value payload in log
  uint32_t vlen;
};

struct BatchOp {
  size_t key_off;  // offsets within the batch payload
  uint32_t klen;
  size_t val_off;
  uint32_t vlen;
  bool is_del;
};

// Walk a batch payload into per-op offsets; false on malformed structure.
bool parse_batch(const uint8_t* p, size_t n, std::vector<BatchOp>* out) {
  if (n < 4) return false;
  uint32_t count;
  memcpy(&count, p, 4);
  // every op carries an 8-byte header: a count the payload cannot hold
  // is rejected before it sizes the reserve
  if (count > (n - 4) / 8) return false;
  size_t cur = 4;
  out->clear();
  out->reserve(count);
  for (uint32_t i = 0; i < count; i++) {
    if (cur + 8 > n) return false;
    uint32_t klen, vlen;
    memcpy(&klen, p + cur, 4);
    memcpy(&vlen, p + cur + 4, 4);
    cur += 8;
    bool is_del = vlen == kDeleteMarker;
    if (klen > (1u << 28) || (!is_del && vlen > (1u << 30))) return false;
    BatchOp op;
    op.key_off = cur;
    op.klen = klen;
    op.is_del = is_del;
    if (cur + klen > n) return false;
    cur += klen;
    op.val_off = cur;
    op.vlen = is_del ? 0 : vlen;
    if (!is_del) {
      if (cur + vlen > n) return false;
      cur += vlen;
    }
    out->push_back(op);
  }
  return cur == n;
}

struct Store {
  std::string path;
  FILE* log = nullptr;
  std::map<std::string, Record> index;
  std::mutex mu;
  uint64_t live_bytes = 0;
  uint64_t total_bytes = 0;

  // Index bookkeeping for one logical op (shared by replay and append).
  void apply_op(const std::string& key, uint64_t voff, uint32_t vlen,
                bool is_del) {
    auto it = index.find(key);
    if (it != index.end()) live_bytes -= it->second.vlen + key.size();
    if (is_del) {
      if (it != index.end()) index.erase(it);
    } else {
      index[key] = Record{voff, vlen};
      live_bytes += vlen + key.size();
    }
  }

  bool replay() {
    FILE* f = fopen(path.c_str(), "rb");
    if (!f) return true;  // fresh store
    uint64_t off = 0, good_end = 0;
    std::vector<uint8_t> buf;
    std::vector<BatchOp> ops;
    for (;;) {
      uint32_t hdr[3];  // klen, vlen, crc
      if (fread(hdr, 1, 12, f) != 12) break;
      uint32_t klen = hdr[0], vlen = hdr[1], crc = hdr[2];
      if (klen == kBatchMarker) {
        // one batch = one record: CRC gates the whole payload, so either
        // every op below lands in the index or none does
        uint32_t payload = vlen;
        if (payload > (1u << 30)) break;
        buf.resize(payload);
        if (payload && fread(buf.data(), 1, payload, f) != payload) break;
        uint32_t want = crc32(buf.data(), payload,
                              crc32(reinterpret_cast<uint8_t*>(hdr), 8));
        if (want != crc) break;  // torn/corrupt tail
        if (!parse_batch(buf.data(), payload, &ops)) break;
        for (const auto& op : ops) {
          std::string key(reinterpret_cast<char*>(buf.data()) + op.key_off,
                          op.klen);
          apply_op(key, off + 12 + op.val_off, op.vlen, op.is_del);
        }
        off += 12 + payload;
        good_end = off;
        continue;
      }
      bool is_del = vlen == kDeleteMarker;
      uint32_t payload = klen + (is_del ? 0 : vlen);
      if (klen > (1u << 28) || (!is_del && vlen > (1u << 30))) break;
      buf.resize(payload);
      if (payload && fread(buf.data(), 1, payload, f) != payload) break;
      uint32_t want = crc32(buf.data(), payload,
                            crc32(reinterpret_cast<uint8_t*>(hdr), 8));
      if (want != crc) break;  // torn/corrupt tail
      std::string key(reinterpret_cast<char*>(buf.data()), klen);
      apply_op(key, off + 12 + klen, is_del ? 0 : vlen, is_del);
      off += 12 + payload;
      good_end = off;
    }
    fclose(f);
    total_bytes = good_end;
    // truncate torn tail so appends start at a clean boundary
    if (good_end > 0) {
      FILE* t = fopen(path.c_str(), "rb+");
      if (t) {
#ifdef _WIN32
#else
        if (ftruncate(fileno(t), static_cast<off_t>(good_end)) != 0) { /* best effort */ }
#endif
        fclose(t);
      }
    }
    return true;
  }

  // A failed/partial fwrite leaves garbage after total_bytes; chop it off so
  // later appends still land where the index expects them.
  void truncate_to_good_end() {
    if (!log) return;
    fflush(log);
#ifndef _WIN32
    if (ftruncate(fileno(log), static_cast<off_t>(total_bytes)) != 0) {
      /* best effort; replay's CRC check still protects readers */
    }
#endif
    fseek(log, 0, SEEK_END);
  }

  bool write_record(const uint32_t hdr_kl, const uint32_t hdr_vl,
                    const uint8_t* payload, size_t plen) {
    uint32_t hdr[3];
    hdr[0] = hdr_kl;
    hdr[1] = hdr_vl;
    hdr[2] = crc32(payload, plen, crc32(reinterpret_cast<uint8_t*>(hdr), 8));
    if (fwrite(hdr, 1, 12, log) != 12 ||
        (plen && fwrite(payload, 1, plen, log) != plen)) {
      truncate_to_good_end();
      return false;
    }
    return true;
  }

  bool append(const std::string& key, const uint8_t* val, uint32_t vlen,
              bool is_del) {
    std::vector<uint8_t> payload(key.size() + (is_del ? 0 : vlen));
    memcpy(payload.data(), key.data(), key.size());
    if (!is_del && vlen) memcpy(payload.data() + key.size(), val, vlen);
    if (!write_record(static_cast<uint32_t>(key.size()),
                      is_del ? kDeleteMarker : vlen,
                      payload.data(), payload.size()))
      return false;
    uint64_t voff = total_bytes + 12 + key.size();
    total_bytes += 12 + payload.size();
    apply_op(key, voff, is_del ? 0 : vlen, is_del);
    return true;
  }

  // Append a whole batch as one record; the index is only touched after the
  // full record hit the log (and optionally fsync'd), so an in-process write
  // failure leaves the store exactly as before the call.
  bool append_batch(const uint8_t* payload, size_t plen, bool do_fsync) {
    std::vector<BatchOp> ops;
    if (plen > (1u << 30) || !parse_batch(payload, plen, &ops)) return false;
    if (!write_record(kBatchMarker, static_cast<uint32_t>(plen), payload,
                      plen))
      return false;
    if (fflush(log) != 0) {
      truncate_to_good_end();
      return false;
    }
#ifndef _WIN32
    if (do_fsync && fsync(fileno(log)) != 0) {
      truncate_to_good_end();
      return false;
    }
#endif
    uint64_t off = total_bytes;
    total_bytes += 12 + plen;
    for (const auto& op : ops) {
      std::string key(reinterpret_cast<const char*>(payload) + op.key_off,
                      op.klen);
      apply_op(key, off + 12 + op.val_off, op.vlen, op.is_del);
    }
    return true;
  }
};

struct Iter {
  std::vector<std::pair<std::string, Record>> items;
  size_t pos = 0;
  Store* store;
  std::vector<uint8_t> val_buf;
};

}  // namespace

extern "C" {

void* kv_open(const char* path) {
  auto* s = new Store();
  s->path = path;
  if (!s->replay()) {
    delete s;
    return nullptr;
  }
  s->log = fopen(path, "ab");
  if (!s->log) {
    delete s;
    return nullptr;
  }
  return s;
}

void kv_close(void* h) {
  auto* s = static_cast<Store*>(h);
  if (s->log) fclose(s->log);
  delete s;
}

int kv_put(void* h, const uint8_t* key, size_t klen, const uint8_t* val,
           size_t vlen) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  return s->append(std::string(reinterpret_cast<const char*>(key), klen), val,
                   static_cast<uint32_t>(vlen), false)
             ? 0
             : -1;
}

// Atomic write batch. `payload` uses the batch wire format
// ([u32 count] then per-op [u32 klen][u32 vlen|0xFFFFFFFF][key][val]);
// the whole batch becomes ONE CRC'd log record applied all-or-nothing on
// replay. `do_fsync` != 0 adds an fsync barrier after the record (the
// commit point for block-import / migration batches). Returns 0 on
// success, -1 on write failure (log truncated back, index untouched),
// -2 on a malformed payload.
int kv_write_batch(void* h, const uint8_t* payload, size_t plen,
                   int do_fsync) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  std::vector<BatchOp> ops;
  if (plen > (1u << 30) || !parse_batch(payload, plen, &ops)) return -2;
  return s->append_batch(payload, plen, do_fsync != 0) ? 0 : -1;
}

int kv_delete(void* h, const uint8_t* key, size_t klen) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  return s->append(std::string(reinterpret_cast<const char*>(key), klen),
                   nullptr, 0, true)
             ? 0
             : -1;
}

// Returns value length, -1 if missing, -2 on read error. Caller provides the
// buffer via kv_get_copy after sizing with kv_get_len (two-step to keep the
// ABI malloc-free).
int64_t kv_get_len(void* h, const uint8_t* key, size_t klen) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  auto it = s->index.find(std::string(reinterpret_cast<const char*>(key), klen));
  if (it == s->index.end()) return -1;
  return it->second.vlen;
}

int64_t kv_get_copy(void* h, const uint8_t* key, size_t klen, uint8_t* out,
                    size_t out_len) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  auto it = s->index.find(std::string(reinterpret_cast<const char*>(key), klen));
  if (it == s->index.end()) return -1;
  if (it->second.vlen > out_len) return -2;
  fflush(s->log);
  FILE* f = fopen(s->path.c_str(), "rb");
  if (!f) return -2;
  if (fseek(f, static_cast<long>(it->second.offset), SEEK_SET) != 0 ||
      fread(out, 1, it->second.vlen, f) != it->second.vlen) {
    fclose(f);
    return -2;
  }
  fclose(f);
  return it->second.vlen;
}

int kv_exists(void* h, const uint8_t* key, size_t klen) {
  return kv_get_len(h, key, klen) >= 0 ? 1 : 0;
}

uint64_t kv_count(void* h) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  return s->index.size();
}

int kv_sync(void* h) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  return fflush(s->log) == 0 ? 0 : -1;
}

// -- ordered prefix iteration ------------------------------------------------

void* kv_iter_prefix(void* h, const uint8_t* prefix, size_t plen) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  auto* it = new Iter();
  it->store = s;
  std::string p(reinterpret_cast<const char*>(prefix), plen);
  for (auto iter = s->index.lower_bound(p); iter != s->index.end(); ++iter) {
    if (iter->first.compare(0, p.size(), p) != 0) break;
    it->items.push_back(*iter);
  }
  return it;
}

// Returns 1 and fills pointers while items remain; 0 at end.
int kv_iter_next(void* hi, const uint8_t** key, size_t* klen,
                 const uint8_t** val, size_t* vlen) {
  auto* it = static_cast<Iter*>(hi);
  if (it->pos >= it->items.size()) return 0;
  const auto& [k, rec] = it->items[it->pos++];
  *key = reinterpret_cast<const uint8_t*>(k.data());
  *klen = k.size();
  it->val_buf.resize(rec.vlen);
  {
    std::lock_guard<std::mutex> lock(it->store->mu);
    fflush(it->store->log);
    FILE* f = fopen(it->store->path.c_str(), "rb");
    if (!f) return 0;
    if (fseek(f, static_cast<long>(rec.offset), SEEK_SET) != 0 ||
        fread(it->val_buf.data(), 1, rec.vlen, f) != rec.vlen) {
      fclose(f);
      return 0;
    }
    fclose(f);
  }
  *val = it->val_buf.data();
  *vlen = it->val_buf.size();
  return 1;
}

void kv_iter_destroy(void* hi) { delete static_cast<Iter*>(hi); }

// Rewrite only live records; returns 0 on success.
int kv_compact(void* h) {
  auto* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->mu);
  fflush(s->log);
  std::string tmp = s->path + ".compact";
  FILE* out = fopen(tmp.c_str(), "wb");
  if (!out) return -1;
  FILE* in = fopen(s->path.c_str(), "rb");
  if (!in) {
    fclose(out);
    return -1;
  }
  std::map<std::string, Record> new_index;
  uint64_t new_total = 0;
  std::vector<uint8_t> val;
  for (const auto& [key, rec] : s->index) {
    val.resize(rec.vlen);
    if (fseek(in, static_cast<long>(rec.offset), SEEK_SET) != 0 ||
        fread(val.data(), 1, rec.vlen, in) != rec.vlen) {
      fclose(in); fclose(out);
      remove(tmp.c_str());
      return -1;
    }
    uint32_t hdr[3];
    hdr[0] = static_cast<uint32_t>(key.size());
    hdr[1] = rec.vlen;
    std::vector<uint8_t> payload(key.size() + rec.vlen);
    memcpy(payload.data(), key.data(), key.size());
    memcpy(payload.data() + key.size(), val.data(), rec.vlen);
    hdr[2] = crc32(payload.data(), payload.size(),
                   crc32(reinterpret_cast<uint8_t*>(hdr), 8));
    fwrite(hdr, 1, 12, out);
    fwrite(payload.data(), 1, payload.size(), out);
    new_index[key] = Record{new_total + 12 + key.size(), rec.vlen};
    new_total += 12 + payload.size();
  }
  fclose(in);
  fclose(out);
  fclose(s->log);
  if (rename(tmp.c_str(), s->path.c_str()) != 0) {
    s->log = fopen(s->path.c_str(), "ab");
    return -1;
  }
  s->index = std::move(new_index);
  s->total_bytes = new_total;
  s->live_bytes = new_total;
  s->log = fopen(s->path.c_str(), "ab");
  return s->log ? 0 : -1;
}

}  // extern "C"
