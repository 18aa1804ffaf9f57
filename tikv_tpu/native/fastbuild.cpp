/* Native MVCC -> columnar builder: the data-loader hot loop.
 *
 * Reference roles: the scan->batch handoff the reference gets from
 * RocksDB's C++ iterators + tidb_query_datatype's row decode
 * (src/coprocessor/dag/storage_impl.rs scan_next feeding
 * LazyBatchColumnVec).  SURVEY.md §7 "Decode on the hot path" calls for
 * host-side decode into dense columnar buffers at native speed; this
 * module is that component: one pass over a CF_WRITE range resolving
 * Percolator versions at read_ts and decoding row payloads straight
 * into int64/float64 buffers the caller wraps as numpy arrays.
 *
 * Formats parsed here (kept in lockstep with the Python codecs):
 *  - engine key: [prefix_skip bytes] 'x' + memcomparable(user_key)
 *                + 8-byte big-endian ~commit_ts   (txn_types.py)
 *  - user key:   't' + be64(table_id^sign) + "_r" + be64(handle^sign)
 *                (codec/keys.py)
 *  - write record: type byte 'P'/'D'/'L'/'R' + varint(start_ts)
 *                [+ 'v' varint(len) short_value] [+ 'R']  (txn_types.py)
 *  - row payload: msgpack map {int column_id: nil|int|float|bin|str}
 *                (codec/row.py)
 *
 * Anything outside this envelope (unknown msgpack tag, malformed key)
 * raises, and the Python caller falls back to the interpreted path.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <deque>
#include <string>
#include <vector>

namespace {

constexpr uint64_t kSignMask = 0x8000000000000000ULL;

inline uint64_t be64(const uint8_t* p) {
  uint64_t v = 0;
  for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
  return v;
}

int read_varu64(const uint8_t* p, Py_ssize_t len, Py_ssize_t* off,
                uint64_t* out) {
  int shift = 0;
  uint64_t v = 0;
  while (*off < len) {
    uint8_t b = p[(*off)++];
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return 0;
    }
    shift += 7;
    if (shift > 63) return -1;
  }
  return -1;
}

/* memcomparable decode (codec/number.py decode_bytes_memcomparable) */
int mc_decode(const uint8_t* p, Py_ssize_t len, Py_ssize_t* off,
              std::string* out) {
  out->clear();
  for (;;) {
    if (*off + 9 > len) return -1;
    uint8_t marker = p[*off + 8];
    int pad = 0xFF - (int)marker;
    if (pad < 0 || pad > 8) return -1;
    out->append(reinterpret_cast<const char*>(p) + *off, 8 - pad);
    *off += 9;
    if (pad != 0) return 0;
  }
}

/* minimal msgpack value (codec/row.py envelope) */
struct MpVal {
  /* EXT: a msgpack ext datum, its type code in ``i`` and its data in
   * ``b`` / ``blen`` (codec/row.py: ExtType(1) is a DECIMAL's text) */
  enum { NIL, INT, FLT, BIN, EXT } type;
  int64_t i;
  double f;
  const uint8_t* b;
  uint32_t blen;
};

int mp_read(const uint8_t* p, Py_ssize_t len, Py_ssize_t* off, MpVal* v) {
  if (*off >= len) return -1;
  uint8_t t = p[(*off)++];
  if (t <= 0x7F) { v->type = MpVal::INT; v->i = t; return 0; }
  if (t >= 0xE0) { v->type = MpVal::INT; v->i = (int8_t)t; return 0; }
  auto need = [&](Py_ssize_t n) { return *off + n <= len; };
  switch (t) {
    case 0xC0: v->type = MpVal::NIL; return 0;
    case 0xC2: v->type = MpVal::INT; v->i = 0; return 0;
    case 0xC3: v->type = MpVal::INT; v->i = 1; return 0;
    case 0xCC: if (!need(1)) return -1;
      v->type = MpVal::INT; v->i = p[(*off)++]; return 0;
    case 0xCD: if (!need(2)) return -1;
      v->type = MpVal::INT; v->i = (p[*off] << 8) | p[*off + 1];
      *off += 2; return 0;
    case 0xCE: if (!need(4)) return -1;
      v->type = MpVal::INT;
      v->i = ((uint32_t)p[*off] << 24) | ((uint32_t)p[*off + 1] << 16) |
             ((uint32_t)p[*off + 2] << 8) | p[*off + 3];
      *off += 4; return 0;
    case 0xCF: if (!need(8)) return -1;
      v->type = MpVal::INT; v->i = (int64_t)be64(p + *off);
      *off += 8; return 0;
    case 0xD0: if (!need(1)) return -1;
      v->type = MpVal::INT; v->i = (int8_t)p[(*off)++]; return 0;
    case 0xD1: if (!need(2)) return -1;
      v->type = MpVal::INT;
      v->i = (int16_t)((p[*off] << 8) | p[*off + 1]); *off += 2; return 0;
    case 0xD2: if (!need(4)) return -1;
      v->type = MpVal::INT;
      v->i = (int32_t)(((uint32_t)p[*off] << 24) |
                       ((uint32_t)p[*off + 1] << 16) |
                       ((uint32_t)p[*off + 2] << 8) | p[*off + 3]);
      *off += 4; return 0;
    case 0xD3: if (!need(8)) return -1;
      v->type = MpVal::INT; v->i = (int64_t)be64(p + *off);
      *off += 8; return 0;
    case 0xCA: { if (!need(4)) return -1;
      uint32_t u = ((uint32_t)p[*off] << 24) |
                   ((uint32_t)p[*off + 1] << 16) |
                   ((uint32_t)p[*off + 2] << 8) | p[*off + 3];
      float f;
      std::memcpy(&f, &u, 4);
      v->type = MpVal::FLT; v->f = f; *off += 4; return 0; }
    case 0xCB: { if (!need(8)) return -1;
      uint64_t u = be64(p + *off);
      std::memcpy(&v->f, &u, 8);
      v->type = MpVal::FLT; *off += 8; return 0; }
    case 0xC4: case 0xD9: { if (!need(1)) return -1;
      uint32_t n = p[(*off)++];
      if (!need(n)) return -1;
      v->type = MpVal::BIN; v->b = p + *off; v->blen = n;
      *off += n; return 0; }
    case 0xC5: case 0xDA: { if (!need(2)) return -1;
      uint32_t n = (p[*off] << 8) | p[*off + 1];
      *off += 2;
      if (!need(n)) return -1;
      v->type = MpVal::BIN; v->b = p + *off; v->blen = n;
      *off += n; return 0; }
    case 0xC6: case 0xDB: { if (!need(4)) return -1;
      uint32_t n = ((uint32_t)p[*off] << 24) | ((uint32_t)p[*off + 1] << 16) |
                   ((uint32_t)p[*off + 2] << 8) | p[*off + 3];
      *off += 4;
      if (!need(n)) return -1;
      v->type = MpVal::BIN; v->b = p + *off; v->blen = n;
      *off += n; return 0; }
    case 0xD4: case 0xD5: case 0xD6: case 0xD7: case 0xD8: {
      uint32_t n = 1u << (t - 0xD4);          /* fixext 1/2/4/8/16 */
      if (!need(1 + (Py_ssize_t)n)) return -1;
      v->type = MpVal::EXT; v->i = (int8_t)p[(*off)++];
      v->b = p + *off; v->blen = n;
      *off += n; return 0; }
    case 0xC7: case 0xC8: case 0xC9: {        /* ext 8/16/32 */
      int w = 1 << (t - 0xC7);
      if (!need(w)) return -1;
      uint32_t n = 0;
      for (int k = 0; k < w; k++) n = (n << 8) | p[(*off)++];
      if (!need(1 + (Py_ssize_t)n)) return -1;
      v->type = MpVal::EXT; v->i = (int8_t)p[(*off)++];
      v->b = p + *off; v->blen = n;
      *off += n; return 0; }
    default:
      if (t >= 0xA0 && t <= 0xBF) {  /* fixstr */
        uint32_t n = t & 0x1F;
        if (!need(n)) return -1;
        v->type = MpVal::BIN; v->b = p + *off; v->blen = n;
        *off += n; return 0;
      }
      return -1;
  }
}

/* A DECIMAL datum's text ("-12.50", codec/row.py: format(d, "f")) as
 * value * 10^scale, exactly: 0 where it has at most ``scale`` digits
 * right of the point and the result fits int64, else -1 (the caller
 * leaves its envelope: the interpreted build keeps such a column as
 * Decimal objects). */
int dec_text_scaled(const uint8_t* p, uint32_t n, int scale, int64_t* out) {
  uint32_t i = 0;
  bool neg = false;
  if (i < n && (p[i] == '-' || p[i] == '+')) { neg = p[i] == '-'; i++; }
  unsigned __int128 acc = 0;
  const unsigned __int128 kMax = (unsigned __int128)1 << 63;
  int frac = -1, digits = 0;
  for (; i < n; i++) {
    uint8_t ch = p[i];
    if (ch == '.') {
      if (frac >= 0) return -1;
      frac = 0;
      continue;
    }
    if (ch < '0' || ch > '9') return -1;      /* exponent, NaN, Infinity */
    if (frac >= 0 && ++frac > scale) {
      if (ch != '0') return -1;               /* beyond the declared scale */
      frac = scale;
      continue;
    }
    acc = acc * 10 + (ch - '0');
    if (acc > kMax) return -1;
    digits++;
  }
  if (!digits) return -1;
  for (int k = frac < 0 ? 0 : frac; k < scale; k++) {
    acc *= 10;
    if (acc > kMax) return -1;
  }
  if (acc > kMax - (neg ? 0 : 1)) return -1;
  *out = neg ? (int64_t)(0 - (uint64_t)acc) : (int64_t)acc;
  return 0;
}

int mp_map_len(const uint8_t* p, Py_ssize_t len, Py_ssize_t* off,
               uint32_t* n) {
  if (*off >= len) return -1;
  uint8_t t = p[(*off)++];
  if ((t & 0xF0) == 0x80) { *n = t & 0x0F; return 0; }
  if (t == 0xDE) {
    if (*off + 2 > len) return -1;
    *n = (p[*off] << 8) | p[*off + 1];
    *off += 2;
    return 0;
  }
  if (t == 0xDF) {
    if (*off + 4 > len) return -1;
    *n = ((uint32_t)p[*off] << 24) | ((uint32_t)p[*off + 1] << 16) |
         ((uint32_t)p[*off + 2] << 8) | p[*off + 3];
    *off += 4;
    return 0;
  }
  return -1;
}

struct Col {
  int64_t id;
  /* 0=int64 1=float64 2=bytes(object) 3=uint64
   * 4=DECIMAL as int64 scaled by 10^scale */
  int kind;
  int scale = 0;
  std::vector<int64_t> i64;
  std::vector<double> f64;
  std::vector<uint64_t> u64;
  PyObject* objs;  /* list, for kind 2 */
  std::vector<uint8_t> valid;
};

PyObject* fail(const char* msg) {
  PyErr_SetString(PyExc_ValueError, msg);
  return nullptr;
}

PyObject* mvcc_build(PyObject*, PyObject* args) {
  /* (keys, vals, read_ts, prefix_skip, col_ids, col_kinds
   *  [, col_scales: a DECIMAL column's scale, 0 for the others]) */
  PyObject *keys_o, *vals_o, *colids_o, *colkinds_o, *colscales_o = nullptr;
  unsigned long long read_ts;
  Py_ssize_t prefix_skip;
  if (!PyArg_ParseTuple(args, "OOKnOO|O", &keys_o, &vals_o, &read_ts,
                        &prefix_skip, &colids_o, &colkinds_o, &colscales_o))
    return nullptr;

  PyObject* keys = PySequence_Fast(keys_o, "keys not a sequence");
  if (!keys) return nullptr;
  PyObject* vals = PySequence_Fast(vals_o, "values not a sequence");
  if (!vals) { Py_DECREF(keys); return nullptr; }
  Py_ssize_t n_in = PySequence_Fast_GET_SIZE(keys);
  if (PySequence_Fast_GET_SIZE(vals) != n_in) {
    Py_DECREF(keys); Py_DECREF(vals);
    return fail("keys/values length mismatch");
  }

  std::vector<Col> cols;
  Py_ssize_t ncols = PySequence_Size(colids_o);
  for (Py_ssize_t c = 0; c < ncols; c++) {
    PyObject* ido = PySequence_GetItem(colids_o, c);
    PyObject* ko = PySequence_GetItem(colkinds_o, c);
    Col col;
    col.id = PyLong_AsLongLong(ido);
    col.kind = (int)PyLong_AsLong(ko);
    col.objs = (col.kind == 2) ? PyList_New(0) : nullptr;
    Py_XDECREF(ido);
    Py_XDECREF(ko);
    if (colscales_o && colscales_o != Py_None) {
      PyObject* so = PySequence_GetItem(colscales_o, c);
      col.scale = so ? (int)PyLong_AsLong(so) : 0;
      Py_XDECREF(so);
    }
    if (col.scale < 0 || col.scale > 18) {
      for (auto& c2 : cols) Py_XDECREF(c2.objs);
      Py_XDECREF(col.objs);
      Py_DECREF(keys); Py_DECREF(vals);
      return fail("decimal scale outside int64");
    }
    cols.push_back(std::move(col));
  }

  std::vector<int64_t> handles;
  /* datums of columns nobody asked for: read past, whatever their kind */
  unsigned long long skipped = 0;
  uint64_t safe_ts = 0;
  std::string user_key, prev_key;
  bool resolved = false;
  PyObject* need_default = PyList_New(0);

  auto cleanup = [&]() {
    for (auto& c : cols) Py_XDECREF(c.objs);
    Py_XDECREF(need_default);
    Py_DECREF(keys);
    Py_DECREF(vals);
  };

  for (Py_ssize_t i = 0; i < n_in; i++) {
    PyObject* ko = PySequence_Fast_GET_ITEM(keys, i);
    PyObject* vo = PySequence_Fast_GET_ITEM(vals, i);
    char* kp;
    Py_ssize_t klen;
    if (PyBytes_AsStringAndSize(ko, &kp, &klen) < 0) {
      cleanup();
      return nullptr;
    }
    const uint8_t* k = reinterpret_cast<const uint8_t*>(kp);
    Py_ssize_t off = prefix_skip;
    if (off >= klen || k[off] != 'x') { cleanup(); return fail("bad key mode"); }
    off += 1;
    if (mc_decode(k, klen - 8, &off, &user_key) < 0 || off != klen - 8) {
      cleanup();
      return fail("bad memcomparable key");
    }
    uint64_t commit_ts = ~be64(k + klen - 8);
    if (commit_ts > safe_ts) safe_ts = commit_ts;
    bool same = (user_key == prev_key);
    if (!same) {
      prev_key = user_key;
      resolved = false;
    }
    if (resolved || commit_ts > read_ts) continue;

    char* vp;
    Py_ssize_t vlen;
    if (PyBytes_AsStringAndSize(vo, &vp, &vlen) < 0) {
      cleanup();
      return nullptr;
    }
    const uint8_t* v = reinterpret_cast<const uint8_t*>(vp);
    if (vlen < 2) { cleanup(); return fail("short write record"); }
    char wt = (char)v[0];
    Py_ssize_t voff = 1;
    uint64_t start_ts;
    if (read_varu64(v, vlen, &voff, &start_ts) < 0) {
      cleanup();
      return fail("bad write start_ts");
    }
    const uint8_t* sval = nullptr;
    uint64_t svlen = 0;
    while (voff < vlen) {
      char tag = (char)v[voff++];
      if (tag == 'v') {
        if (read_varu64(v, vlen, &voff, &svlen) < 0 ||
            voff + (Py_ssize_t)svlen > vlen) {
          cleanup();
          return fail("bad short value");
        }
        sval = v + voff;
        voff += svlen;
      } else if (tag == 'R') {
        /* overlapped rollback marker on a committed write */
      } else {
        cleanup();
        return fail("bad write tag");
      }
    }
    if (wt == 'L' || wt == 'R') continue;   /* next version */
    resolved = true;
    if (wt == 'D') continue;                /* deleted at read_ts */
    if (wt != 'P') { cleanup(); return fail("bad write type"); }

    /* visible PUT: decode handle (user key 't'+8+'_r'+8) */
    if (user_key.size() < 19) { cleanup(); return fail("short record key"); }
    const uint8_t* uk = reinterpret_cast<const uint8_t*>(user_key.data());
    int64_t handle = (int64_t)(be64(uk + 11) - kSignMask);
    Py_ssize_t row = (Py_ssize_t)handles.size();
    handles.push_back(handle);
    for (auto& c : cols) {
      c.valid.push_back(0);
      switch (c.kind) {
        case 0: case 4: c.i64.push_back(0); break;
        case 1: c.f64.push_back(0.0); break;
        case 3: c.u64.push_back(0); break;
        case 2:
          if (PyList_Append(c.objs, Py_None) < 0) { cleanup(); return nullptr; }
          break;
      }
    }
    if (sval == nullptr) {
      /* big value lives in CF_DEFAULT at (key, start_ts): patched by
       * the Python caller (rare: values > SHORT_VALUE_MAX_LEN) */
      PyObject* t = Py_BuildValue(
          "nKy#", row, (unsigned long long)start_ts, user_key.data(),
          (Py_ssize_t)user_key.size());
      if (!t || PyList_Append(need_default, t) < 0) {
        Py_XDECREF(t);
        cleanup();
        return nullptr;
      }
      Py_DECREF(t);
      continue;
    }
    /* decode msgpack row map into the column slots */
    Py_ssize_t moff = 0;
    uint32_t pairs;
    if (mp_map_len(sval, (Py_ssize_t)svlen, &moff, &pairs) < 0) {
      cleanup();
      return fail("bad row map");
    }
    for (uint32_t e = 0; e < pairs; e++) {
      MpVal cid, val;
      if (mp_read(sval, (Py_ssize_t)svlen, &moff, &cid) < 0 ||
          cid.type != MpVal::INT ||
          mp_read(sval, (Py_ssize_t)svlen, &moff, &val) < 0) {
        cleanup();
        return fail("bad row datum");
      }
      bool wanted = false;
      for (auto& c : cols) {
        if (c.id != cid.i) continue;
        wanted = true;
        if (val.type == MpVal::NIL) break;
        c.valid[row] = 1;
        switch (c.kind) {
          case 4:
            if (val.type != MpVal::EXT || val.i != 1 ||
                dec_text_scaled(val.b, val.blen, c.scale, &c.i64[row]) < 0) {
              cleanup();
              return fail("decimal datum outside the column's scale");
            }
            break;
          case 0:
            if (val.type == MpVal::INT) c.i64[row] = val.i;
            else if (val.type == MpVal::FLT) c.i64[row] = (int64_t)val.f;
            else { cleanup(); return fail("type mismatch int col"); }
            break;
          case 1:
            if (val.type == MpVal::FLT) c.f64[row] = val.f;
            else if (val.type == MpVal::INT) c.f64[row] = (double)val.i;
            else { cleanup(); return fail("type mismatch real col"); }
            break;
          case 3:
            if (val.type == MpVal::INT) c.u64[row] = (uint64_t)val.i;
            else { cleanup(); return fail("type mismatch u64 col"); }
            break;
          case 2: {
            if (val.type != MpVal::BIN) {
              cleanup();
              return fail("type mismatch bytes col");
            }
            PyObject* b = PyBytes_FromStringAndSize(
                reinterpret_cast<const char*>(val.b), val.blen);
            if (!b) { cleanup(); return nullptr; }
            /* PyList_SetItem steals b's ref even on failure */
            if (PyList_SetItem(c.objs, row, b) < 0) {
              cleanup();
              return nullptr;
            }
            break;
          }
        }
        break;
      }
      if (!wanted) skipped++;
    }
  }

  Py_ssize_t n = (Py_ssize_t)handles.size();
  PyObject* handles_b = PyByteArray_FromStringAndSize(
      reinterpret_cast<const char*>(handles.data()), n * 8);
  PyObject* out_cols = PyList_New(0);
  if (!handles_b || !out_cols) {
    Py_XDECREF(handles_b);
    Py_XDECREF(out_cols);
    cleanup();
    return nullptr;
  }
  for (auto& c : cols) {
    PyObject* payload;
    if (c.kind == 2) {
      payload = c.objs;
      Py_INCREF(payload);
    } else if (c.kind == 1) {
      payload = PyByteArray_FromStringAndSize(
          reinterpret_cast<const char*>(c.f64.data()), n * 8);
    } else if (c.kind == 3) {
      payload = PyByteArray_FromStringAndSize(
          reinterpret_cast<const char*>(c.u64.data()), n * 8);
    } else {
      payload = PyByteArray_FromStringAndSize(
          reinterpret_cast<const char*>(c.i64.data()), n * 8);
    }
    PyObject* validity = PyByteArray_FromStringAndSize(
        reinterpret_cast<const char*>(c.valid.data()), n);
    PyObject* tup = (payload && validity)
        ? Py_BuildValue("(LiOO)", (long long)c.id, c.kind, payload, validity)
        : nullptr;
    Py_XDECREF(payload);
    Py_XDECREF(validity);
    if (!tup || PyList_Append(out_cols, tup) < 0) {
      Py_XDECREF(tup);
      Py_DECREF(handles_b);
      Py_DECREF(out_cols);
      cleanup();
      return nullptr;
    }
    Py_DECREF(tup);
  }
  PyObject* ret = Py_BuildValue("{s:O,s:n,s:K,s:O,s:O,s:K}",
                                "handles", handles_b, "n", n,
                                "safe_ts", (unsigned long long)safe_ts,
                                "cols", out_cols,
                                "need_default", need_default,
                                "skipped_datums", skipped);
  Py_DECREF(handles_b);
  Py_DECREF(out_cols);
  cleanup();  /* drops our refs; ret holds its own */
  return ret;
}

/* ------------------------------------------------------------------ *
 * Flat-plane MVCC parse — the device-resolve feed (device/mvcc.py).
 *
 * Where mvcc_build resolves versions AND decodes rows in one host pass,
 * this export only PARSES: every CF_WRITE version becomes one row of a
 * set of flat, fixed-width planes (key-ordinal segments, commit_ts,
 * start_ts, write type, per-column datum planes) that upload H2D as-is,
 * so newest-committed-version selection — a segmented arg-max over
 * commit_ts — runs on the accelerator instead of in this loop.  The
 * core loop holds NO Python objects (key/value pointers are snapshotted
 * first), so it runs with the GIL RELEASED and a concurrent SST encode
 * or ingest RPC makes real progress — the property the streaming cold
 * pipeline (copr/stream_build.py) is built on.
 *
 * Envelope: numeric columns only (kinds 0=int64, 1=float64, 3=uint64 —
 * bytes columns cannot live in device planes); PUTs without a short
 * value are reported in need_default for the caller's CF_DEFAULT patch.
 *
 * Two schema modes:
 *  - explicit (col_ids non-empty): planes for exactly those columns,
 *    datums coerced to the requested kinds (the cold-build path, which
 *    knows the scan schema);
 *  - DISCOVERY (col_ids empty): the streaming ingest path has no
 *    schema yet — every column id seen in any row payload mints a
 *    plane, kind inferred from its first non-NIL datum (INT->0,
 *    FLT->1; BIN is out of envelope).  The consumer reconciles the
 *    discovered planes against the query schema at build time
 *    (device/mvcc.py align_planes).
 */

struct ParseErr {
  const char* msg = nullptr;
};

struct NeedDefault {
  int64_t row;
  uint64_t start_ts;
  std::string ukey;
};

PyObject* mvcc_parse_planes(PyObject*, PyObject* args) {
  PyObject *keys_o, *vals_o, *colids_o, *colkinds_o;
  Py_ssize_t prefix_skip;
  int release_gil = 1;
  if (!PyArg_ParseTuple(args, "OOnOO|p", &keys_o, &vals_o, &prefix_skip,
                        &colids_o, &colkinds_o, &release_gil))
    return nullptr;
  PyObject* keys = PySequence_Fast(keys_o, "keys not a sequence");
  if (!keys) return nullptr;
  PyObject* vals = PySequence_Fast(vals_o, "values not a sequence");
  if (!vals) { Py_DECREF(keys); return nullptr; }
  Py_ssize_t n_in = PySequence_Fast_GET_SIZE(keys);
  if (PySequence_Fast_GET_SIZE(vals) != n_in) {
    Py_DECREF(keys); Py_DECREF(vals);
    return fail("keys/values length mismatch");
  }

  Py_ssize_t ncols = PySequence_Size(colids_o);
  bool discover = (ncols == 0);   /* streaming mode: no schema yet */
  std::vector<int64_t> col_ids(ncols);
  std::vector<int> col_kinds(ncols);
  for (Py_ssize_t c = 0; c < ncols; c++) {
    PyObject* ido = PySequence_GetItem(colids_o, c);
    PyObject* ko = PySequence_GetItem(colkinds_o, c);
    col_ids[c] = PyLong_AsLongLong(ido);
    col_kinds[c] = (int)PyLong_AsLong(ko);
    Py_XDECREF(ido); Py_XDECREF(ko);
    if (col_kinds[c] != 0 && col_kinds[c] != 1 && col_kinds[c] != 3) {
      Py_DECREF(keys); Py_DECREF(vals);
      return fail("plane parse supports numeric kinds only");
    }
  }

  /* pass 1 (GIL held): snapshot raw (ptr, len) for every key/value */
  std::vector<const uint8_t*> kp(n_in), vp(n_in);
  std::vector<Py_ssize_t> kl(n_in), vl(n_in);
  for (Py_ssize_t i = 0; i < n_in; i++) {
    char* p;
    Py_ssize_t l;
    if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(keys, i), &p,
                                &l) < 0) {
      Py_DECREF(keys); Py_DECREF(vals);
      return nullptr;
    }
    kp[i] = reinterpret_cast<const uint8_t*>(p);
    kl[i] = l;
    if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(vals, i), &p,
                                &l) < 0) {
      Py_DECREF(keys); Py_DECREF(vals);
      return nullptr;
    }
    vp[i] = reinterpret_cast<const uint8_t*>(p);
    vl[i] = l;
  }

  /* pass 2 (GIL released): parse into preallocated flat planes */
  std::vector<uint64_t> commit_ts(n_in), start_ts(n_in);
  std::vector<uint8_t> wtype(n_in), has_payload(n_in, 0);
  std::vector<int32_t> seg_id(n_in);
  std::vector<int64_t> handles;        /* per key */
  std::vector<int64_t> seg_start;      /* n_keys + 1 offsets */
  handles.reserve(n_in);
  seg_start.reserve(n_in + 1);
  struct PlaneCol {
    int kind;
    std::vector<int64_t> i64;
    std::vector<double> f64;
    std::vector<uint64_t> u64;
    std::vector<uint8_t> valid;
  };
  std::vector<PlaneCol> planes(ncols);
  for (Py_ssize_t c = 0; c < ncols; c++) {
    planes[c].kind = col_kinds[c];
    planes[c].valid.assign(n_in, 0);
    if (col_kinds[c] == 1) planes[c].f64.assign(n_in, 0.0);
    else if (col_kinds[c] == 3) planes[c].u64.assign(n_in, 0);
    else planes[c].i64.assign(n_in, 0);
  }
  std::vector<NeedDefault> need;
  uint64_t safe_ts = 0;
  int64_t table_id = 0;
  ParseErr err;

  /* release_gil=0: the cold-build path on a single-CPU box — there,
   * yielding the GIL only hands the core to the node's background
   * tick threads and the parse's wall time balloons (measured 3.8s →
   * 18s at 10M versions); the host builder it replaces held the GIL
   * for its whole pass too.  The streaming worker always releases:
   * its entire point is letting the apply loop make progress. */
  PyThreadState* _save_ts = nullptr;
  if (release_gil) _save_ts = PyEval_SaveThread();
  std::string user_key, prev_key;
  for (Py_ssize_t i = 0; i < n_in && !err.msg; i++) {
    const uint8_t* k = kp[i];
    Py_ssize_t klen = kl[i];
    Py_ssize_t off = prefix_skip;
    if (off >= klen || k[off] != 'x') { err.msg = "bad key mode"; break; }
    off += 1;
    if (mc_decode(k, klen - 8, &off, &user_key) < 0 || off != klen - 8) {
      err.msg = "bad memcomparable key";
      break;
    }
    uint64_t cts = ~be64(k + klen - 8);
    if (cts > safe_ts) safe_ts = cts;
    if (user_key.size() != 19 || user_key[0] != 't' ||
        user_key[9] != '_' || user_key[10] != 'r') {
      err.msg = "not a record key";     /* index keys: out of envelope */
      break;
    }
    const uint8_t* uk = reinterpret_cast<const uint8_t*>(user_key.data());
    int64_t tid = (int64_t)(be64(uk + 1) - kSignMask);
    if (handles.empty()) table_id = tid;
    else if (tid != table_id) { err.msg = "mixed tables"; break; }
    if (user_key != prev_key) {
      prev_key = user_key;
      handles.push_back((int64_t)(be64(uk + 11) - kSignMask));
      seg_start.push_back((int64_t)i);
    }
    seg_id[i] = (int32_t)(handles.size() - 1);
    commit_ts[i] = cts;

    const uint8_t* v = vp[i];
    Py_ssize_t vlen = vl[i];
    if (vlen < 2) { err.msg = "short write record"; break; }
    char wt = (char)v[0];
    Py_ssize_t voff = 1;
    uint64_t sts;
    if (read_varu64(v, vlen, &voff, &sts) < 0) {
      err.msg = "bad write start_ts";
      break;
    }
    start_ts[i] = sts;
    const uint8_t* sval = nullptr;
    uint64_t svlen = 0;
    while (voff < vlen) {
      char tag = (char)v[voff++];
      if (tag == 'v') {
        if (read_varu64(v, vlen, &voff, &svlen) < 0 ||
            voff + (Py_ssize_t)svlen > vlen) {
          err.msg = "bad short value";
          break;
        }
        sval = v + voff;
        voff += svlen;
      } else if (tag == 'R') {
        /* overlapped rollback marker on a committed write */
      } else {
        err.msg = "bad write tag";
        break;
      }
    }
    if (err.msg) break;
    uint8_t code;
    switch (wt) {
      case 'P': code = 0; break;
      case 'D': code = 1; break;
      case 'L': code = 2; break;
      case 'R': code = 3; break;
      default: err.msg = "bad write type"; code = 0; break;
    }
    if (err.msg) break;
    wtype[i] = code;
    if (code != 0) continue;            /* only PUTs carry row payloads */
    if (sval == nullptr) {
      need.push_back(NeedDefault{(int64_t)i, sts, user_key});
      continue;
    }
    has_payload[i] = 1;
    Py_ssize_t moff = 0;
    uint32_t pairs;
    if (mp_map_len(sval, (Py_ssize_t)svlen, &moff, &pairs) < 0) {
      err.msg = "bad row map";
      break;
    }
    for (uint32_t e = 0; e < pairs && !err.msg; e++) {
      MpVal cid, val;
      if (mp_read(sval, (Py_ssize_t)svlen, &moff, &cid) < 0 ||
          cid.type != MpVal::INT ||
          mp_read(sval, (Py_ssize_t)svlen, &moff, &val) < 0) {
        err.msg = "bad row datum";
        break;
      }
      Py_ssize_t c = 0;
      for (; c < ncols; c++)
        if (col_ids[c] == cid.i) break;
      if (c == ncols) {
        if (!discover || val.type == MpVal::NIL) continue;
        /* discovery: mint a plane on first sight, kind from the datum
         * (all-NIL columns never materialize — the consumer
         * synthesizes an invalid plane for them) */
        int kind;
        if (val.type == MpVal::INT) kind = 0;
        else if (val.type == MpVal::FLT) kind = 1;
        else { err.msg = "bytes col out of plane envelope"; break; }
        col_ids.push_back(cid.i);
        col_kinds.push_back(kind);
        planes.emplace_back();
        PlaneCol& np_ = planes.back();
        np_.kind = kind;
        np_.valid.assign(n_in, 0);
        if (kind == 1) np_.f64.assign(n_in, 0.0);
        else np_.i64.assign(n_in, 0);
        ncols = (Py_ssize_t)col_ids.size();
      }
      PlaneCol& pc = planes[c];
      if (val.type == MpVal::NIL) continue;
      switch (pc.kind) {
        case 0:
          if (val.type == MpVal::INT) pc.i64[i] = val.i;
          else if (val.type == MpVal::FLT) pc.i64[i] = (int64_t)val.f;
          else err.msg = "type mismatch int col";
          break;
        case 1:
          if (val.type == MpVal::FLT) pc.f64[i] = val.f;
          else if (val.type == MpVal::INT) pc.f64[i] = (double)val.i;
          else err.msg = "type mismatch real col";
          break;
        case 3:
          if (val.type == MpVal::INT) pc.u64[i] = (uint64_t)val.i;
          else err.msg = "type mismatch u64 col";
          break;
      }
      if (!err.msg) pc.valid[i] = 1;
    }
  }
  if (_save_ts) PyEval_RestoreThread(_save_ts);

  Py_DECREF(keys);
  Py_DECREF(vals);
  if (err.msg) return fail(err.msg);
  seg_start.push_back((int64_t)n_in);

  auto as_bytes = [](const void* p, size_t nbytes) {
    return PyByteArray_FromStringAndSize(
        reinterpret_cast<const char*>(p), (Py_ssize_t)nbytes);
  };
  PyObject* nd = PyList_New(0);
  if (!nd) return nullptr;
  for (auto& d : need) {
    PyObject* t = Py_BuildValue("LKy#", (long long)d.row,
                                (unsigned long long)d.start_ts,
                                d.ukey.data(), (Py_ssize_t)d.ukey.size());
    if (!t || PyList_Append(nd, t) < 0) {
      Py_XDECREF(t);
      Py_DECREF(nd);
      return nullptr;
    }
    Py_DECREF(t);
  }
  PyObject* out_cols = PyList_New(0);
  if (!out_cols) { Py_DECREF(nd); return nullptr; }
  for (Py_ssize_t c = 0; c < ncols; c++) {
    PlaneCol& pc = planes[c];
    PyObject* payload =
        pc.kind == 1 ? as_bytes(pc.f64.data(), (size_t)n_in * 8)
        : pc.kind == 3 ? as_bytes(pc.u64.data(), (size_t)n_in * 8)
                       : as_bytes(pc.i64.data(), (size_t)n_in * 8);
    PyObject* validity = as_bytes(pc.valid.data(), (size_t)n_in);
    PyObject* tup = (payload && validity)
        ? Py_BuildValue("(LiOO)", (long long)col_ids[c], pc.kind,
                        payload, validity)
        : nullptr;
    Py_XDECREF(payload);
    Py_XDECREF(validity);
    if (!tup || PyList_Append(out_cols, tup) < 0) {
      Py_XDECREF(tup);
      Py_DECREF(nd);
      Py_DECREF(out_cols);
      return nullptr;
    }
    Py_DECREF(tup);
  }
  Py_ssize_t n_keys = (Py_ssize_t)handles.size();
  PyObject* ret = Py_BuildValue(
      "{s:n,s:n,s:L,s:K,s:N,s:N,s:N,s:N,s:N,s:N,s:N,s:N,s:N}",
      "n_ver", n_in, "n_keys", n_keys, "table_id", (long long)table_id,
      "safe_ts", (unsigned long long)safe_ts,
      "commit_ts", as_bytes(commit_ts.data(), (size_t)n_in * 8),
      "start_ts", as_bytes(start_ts.data(), (size_t)n_in * 8),
      "wtype", as_bytes(wtype.data(), (size_t)n_in),
      "has_payload", as_bytes(has_payload.data(), (size_t)n_in),
      "seg_id", as_bytes(seg_id.data(), (size_t)n_in * 4),
      "handles", as_bytes(handles.data(), (size_t)n_keys * 8),
      "seg_start", as_bytes(seg_start.data(), (size_t)(n_keys + 1) * 8),
      "cols", out_cols, "need_default", nd);
  return ret;
}

/* crc64-xz (ECMA-182 reflected, check 0x995DC9BBDF1939FA — what the
 * reference's crc64fast computes), table-driven; XOR-folded over KV
 * pairs so the checksum is order-independent and composes across
 * regions (src/coprocessor/checksum.rs role). */
uint64_t g_crc64_table[256];
bool g_crc64_ready = false;

void crc64_init() {
  const uint64_t poly = 0xC96C5795D7870F42ULL;
  for (int i = 0; i < 256; i++) {
    uint64_t crc = (uint64_t)i;
    for (int b = 0; b < 8; b++)
      crc = (crc & 1) ? (crc >> 1) ^ poly : crc >> 1;
    g_crc64_table[i] = crc;
  }
  g_crc64_ready = true;
}

inline uint64_t crc64_update(uint64_t crc, const uint8_t* p,
                             Py_ssize_t n) {
  for (Py_ssize_t i = 0; i < n; i++)
    crc = (crc >> 8) ^ g_crc64_table[(crc ^ p[i]) & 0xFF];
  return crc;
}

PyObject* checksum_pairs(PyObject*, PyObject* args) {
  PyObject *keys_o, *vals_o;
  if (!PyArg_ParseTuple(args, "OO", &keys_o, &vals_o)) return nullptr;
  if (!g_crc64_ready) crc64_init();
  PyObject* keys = PySequence_Fast(keys_o, "keys not a sequence");
  if (!keys) return nullptr;
  PyObject* vals = PySequence_Fast(vals_o, "values not a sequence");
  if (!vals) { Py_DECREF(keys); return nullptr; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(keys);
  if (PySequence_Fast_GET_SIZE(vals) != n) {
    Py_DECREF(keys); Py_DECREF(vals);
    return fail("keys/values length mismatch");
  }
  uint64_t folded = 0;
  unsigned long long total_bytes = 0;
  for (Py_ssize_t i = 0; i < n; i++) {
    char *kp, *vp;
    Py_ssize_t klen, vlen;
    if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(keys, i), &kp,
                                &klen) < 0 ||
        PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(vals, i), &vp,
                                &vlen) < 0) {
      Py_DECREF(keys); Py_DECREF(vals);
      return nullptr;
    }
    uint64_t crc = ~0ULL;
    crc = crc64_update(crc, reinterpret_cast<const uint8_t*>(kp), klen);
    crc = crc64_update(crc, reinterpret_cast<const uint8_t*>(vp), vlen);
    folded ^= ~crc;
    total_bytes += (unsigned long long)(klen + vlen);
  }
  Py_DECREF(keys);
  Py_DECREF(vals);
  return Py_BuildValue("(KK)", (unsigned long long)folded, total_bytes);
}

/* ------------------------------------------------------------------ *
 * Bulk MVCC SST builder (client side of the ImportSST path).
 *
 * Reference role: TiDB Lightning / BR's native row encoder feeding
 * sst_importer (components/sst_importer/src/sst_writer.rs) — the
 * reference builds sorted SSTs in Rust at millions of rows/s; the
 * Python per-row encode path caps at ~80k rows/s, so bulk load gets
 * this native builder emitting the v2 SST container directly:
 *
 *   b"TKVSST2\n" + msgpack [[cf, [key...], [val...]], ...] + crc32(BE)
 *
 * Per row (formats mirror codec/number.py, codec/keys.py,
 * storage/txn_types.py Write.to_bytes / append_ts and codec/row.py's
 * msgpack envelope — all asserted byte-equal in tests):
 *   user_key = 't' + be64(table_id^2^63) + "_r" + be64(handle^2^63)
 *   enc      = 'x' + memcomparable(user_key)
 *   write-CF key = enc + be64(2^64-1 - commit_ts)
 *   payload  = msgpack {col_id: nil|int|double}
 *   short payloads inline:  'P' varu64(start_ts) 'v' varu64(len) payload
 *   long payloads split:    default-CF (enc + be64(~start_ts), payload)
 * ------------------------------------------------------------------ */

inline void put_be64(std::string* out, uint64_t v) {
  for (int i = 7; i >= 0; i--) out->push_back((char)((v >> (8 * i)) & 0xFF));
}

inline void put_be32(std::string* out, uint32_t v) {
  for (int i = 3; i >= 0; i--) out->push_back((char)((v >> (8 * i)) & 0xFF));
}

inline void put_varu64(std::string* out, uint64_t v) {
  while (v >= 0x80) {
    out->push_back((char)((v & 0x7F) | 0x80));
    v >>= 7;
  }
  out->push_back((char)v);
}

/* msgpack minimal int encode — byte-identical to msgpack-python packb.
 * The one definition of the widths: ``p`` has room for 9 bytes, the
 * return is one past the last byte written. */
inline char* mp_write_be(char* p, uint8_t tag, uint64_t v, int nbytes) {
  *p++ = (char)tag;
  for (int i = nbytes - 1; i >= 0; i--) *p++ = (char)((v >> (8 * i)) & 0xFF);
  return p;
}

inline char* mp_write_uint(char* p, uint64_t u) {
  if (u <= 0x7F) { *p++ = (char)u; return p; }
  if (u <= 0xFF) return mp_write_be(p, 0xCC, u, 1);
  if (u <= 0xFFFF) return mp_write_be(p, 0xCD, u, 2);
  if (u <= 0xFFFFFFFFULL) return mp_write_be(p, 0xCE, u, 4);
  return mp_write_be(p, 0xCF, u, 8);
}

inline char* mp_write_int(char* p, int64_t v) {
  if (v >= 0) return mp_write_uint(p, (uint64_t)v);
  if (v >= -32) { *p++ = (char)(int8_t)v; return p; }
  if (v >= -128) return mp_write_be(p, 0xD0, (uint64_t)v, 1);
  if (v >= -32768) return mp_write_be(p, 0xD1, (uint64_t)v, 2);
  if (v >= -2147483648LL) return mp_write_be(p, 0xD2, (uint64_t)v, 4);
  return mp_write_be(p, 0xD3, (uint64_t)v, 8);
}

inline void mp_put_int(std::string* out, int64_t v) {
  char b[9];
  out->append(b, mp_write_int(b, v) - b);
}

inline void mp_put_bin(std::string* out, const uint8_t* p, uint32_t n) {
  if (n <= 0xFF) { out->push_back((char)0xC4); out->push_back((char)n); }
  else if (n <= 0xFFFF) { out->push_back((char)0xC5);
    out->push_back((char)(n >> 8)); out->push_back((char)(n & 0xFF)); }
  else { out->push_back((char)0xC6); put_be32(out, n); }
  out->append(reinterpret_cast<const char*>(p), n);
}

inline void mc_encode(std::string* out, const uint8_t* p, Py_ssize_t n) {
  for (Py_ssize_t i = 0; i <= n; i += 8) {
    Py_ssize_t take = n - i < 8 ? n - i : 8;
    out->append(reinterpret_cast<const char*>(p) + i, take);
    for (Py_ssize_t j = take; j < 8; j++) out->push_back('\0');
    out->push_back((char)(0xFF - (8 - take)));
  }
}

/* crc32 (zlib polynomial, matches Python zlib.crc32) */
static uint32_t g_crc32_table[256];
static bool g_crc32_ready = false;
void crc32_init() {
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++)
      c = (c & 1) ? (0xEDB88320u ^ (c >> 1)) : (c >> 1);
    g_crc32_table[i] = c;
  }
  g_crc32_ready = true;
}

inline uint32_t crc32_buf(const uint8_t* p, size_t n) {
  if (!g_crc32_ready) crc32_init();
  uint32_t c = 0xFFFFFFFFu;
  for (size_t i = 0; i < n; i++)
    c = g_crc32_table[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

PyObject* build_mvcc_sst(PyObject*, PyObject* args) {
  /* (table_id, handles_i64_bytes, col_ids tuple, col_kinds tuple,
     col_bufs tuple of bytes, col_valid tuple of bytes-or-None,
     commit_ts, start_ts [, col_aux tuple]) -> v2 sst blob.
     Kinds, each byte-identical to codec/row.py encode_row's datum:
       0 int64 (a packed date core is one: a positive int)
       1 float64
       2 DECIMAL: int64 scaled by 10^aux (aux: the scale, an int)
                  -> ExtType(1, format(d, "f"))
       3 bytes:   buf is the concatenated values, aux the n+1 int64
                  offsets into it, as bytes -> bin */
  long long table_id, commit_ts, start_ts;
  PyObject *handles_o, *ids_o, *kinds_o, *bufs_o, *valid_o,
      *aux_o = nullptr;
  if (!PyArg_ParseTuple(args, "LOOOOOLL|O", &table_id, &handles_o, &ids_o,
                        &kinds_o, &bufs_o, &valid_o, &commit_ts,
                        &start_ts, &aux_o))
    return nullptr;
  char* hp;
  Py_ssize_t hlen;
  if (PyBytes_AsStringAndSize(handles_o, &hp, &hlen) < 0) return nullptr;
  Py_ssize_t n = hlen / 8;
  const int64_t* handles = reinterpret_cast<const int64_t*>(hp);
  Py_ssize_t ncols = PySequence_Size(ids_o);
  if (ncols > 0xFFFF) return fail("too many columns");   /* map16 limit */
  std::vector<int64_t> ids(ncols);
  std::vector<int> kinds(ncols);
  std::vector<const uint8_t*> bufs(ncols);
  std::vector<const uint8_t*> valid(ncols, nullptr);
  std::vector<int> scales(ncols, 0);
  std::vector<const int64_t*> offsets(ncols, nullptr);
  std::vector<int64_t> pow10(19, 1);
  for (int k = 1; k < 19; k++) pow10[k] = pow10[k - 1] * 10;
  for (Py_ssize_t c = 0; c < ncols; c++) {
    PyObject* io = PySequence_GetItem(ids_o, c);
    PyObject* ko = PySequence_GetItem(kinds_o, c);
    ids[c] = PyLong_AsLongLong(io);
    kinds[c] = (int)PyLong_AsLong(ko);
    Py_XDECREF(io); Py_XDECREF(ko);
    PyObject* bo = PySequence_GetItem(bufs_o, c);
    char* bp; Py_ssize_t blen;
    if (PyBytes_AsStringAndSize(bo, &bp, &blen) < 0) {
      Py_XDECREF(bo); return nullptr;
    }
    if (kinds[c] != 3 && blen < n * 8) {
      Py_XDECREF(bo); return fail("short column buffer");
    }
    bufs[c] = reinterpret_cast<const uint8_t*>(bp);
    Py_XDECREF(bo);   /* caller keeps the bytes alive via the tuple */
    if (kinds[c] == 2 || kinds[c] == 3) {
      PyObject* ao = aux_o ? PySequence_GetItem(aux_o, c) : nullptr;
      if (!ao) { PyErr_Clear(); return fail("column kind needs its aux"); }
      if (kinds[c] == 2) {
        scales[c] = (int)PyLong_AsLong(ao);
        Py_DECREF(ao);
        if (scales[c] < 0 || scales[c] > 18)
          return fail("decimal scale outside int64");
      } else {
        char* ap; Py_ssize_t alen;
        if (PyBytes_AsStringAndSize(ao, &ap, &alen) < 0) {
          Py_DECREF(ao); return nullptr;
        }
        Py_DECREF(ao);
        if (alen < (n + 1) * 8) return fail("short offsets buffer");
        offsets[c] = reinterpret_cast<const int64_t*>(ap);
        if (n && (offsets[c][0] < 0 || offsets[c][n] > blen))
          return fail("offsets outside the bytes buffer");
        for (Py_ssize_t i = 0; i < n; i++)
          if (offsets[c][i + 1] < offsets[c][i])
            return fail("offsets not ascending");
      }
    } else if (kinds[c] != 0 && kinds[c] != 1) {
      return fail("unknown column kind");
    }
    PyObject* vo = PySequence_GetItem(valid_o, c);
    if (vo != Py_None) {
      char* vp; Py_ssize_t vlen;
      if (PyBytes_AsStringAndSize(vo, &vp, &vlen) < 0) {
        Py_XDECREF(vo); return nullptr;
      }
      if (vlen < n) { Py_XDECREF(vo); return fail("short validity buffer"); }
      valid[c] = reinterpret_cast<const uint8_t*>(vp);
    }
    Py_XDECREF(vo);
  }

  const uint64_t TSMAX = ~0ULL;
  std::string wkeys, wvals, dkeys, dvals;   /* concatenated msgpack bins */
  wkeys.reserve((size_t)n * 40);
  wvals.reserve((size_t)n * 32);
  uint64_t n_w = 0, n_d = 0;
  std::string ukey, enc, payload, rec;
  /* the encode loop touches only the raw buffers snapshotted above
   * (the caller's tuples keep them alive), so it runs with the GIL
   * RELEASED: the bench loader's build-ahead thread encodes the next
   * chunk while the ingest RPC (and the server's parse/apply, in the
   * in-process test topology) make real progress — serializing them
   * was the measured loader ceiling. */
  Py_BEGIN_ALLOW_THREADS
  for (Py_ssize_t i = 0; i < n; i++) {
    ukey.clear();
    ukey.push_back('t');
    put_be64(&ukey, (uint64_t)table_id ^ 0x8000000000000000ULL);
    ukey.push_back('_'); ukey.push_back('r');
    put_be64(&ukey, (uint64_t)handles[i] ^ 0x8000000000000000ULL);
    enc.clear();
    enc.push_back('x');
    mc_encode(&enc, reinterpret_cast<const uint8_t*>(ukey.data()),
              (Py_ssize_t)ukey.size());
    payload.clear();
    if (ncols <= 15) {
      payload.push_back((char)(0x80 | (ncols & 0x0F)));
    } else {
      /* fixmap tops out at 15 entries; wider rows take map16 (0xDE),
         which mp_map_len and msgpack both decode */
      payload.push_back((char)0xDE);
      payload.push_back((char)((ncols >> 8) & 0xFF));
      payload.push_back((char)(ncols & 0xFF));
    }
    for (Py_ssize_t c = 0; c < ncols; c++) {
      mp_put_int(&payload, ids[c]);
      if (valid[c] && !valid[c][i]) {
        payload.push_back((char)0xC0);                /* nil */
      } else if (kinds[c] == 1) {
        payload.push_back((char)0xCB);                /* float64 */
        uint64_t u;
        std::memcpy(&u, bufs[c] + 8 * i, 8);
        put_be64(&payload, u);
      } else if (kinds[c] == 3) {
        mp_put_bin(&payload, bufs[c] + offsets[c][i],
                   (uint32_t)(offsets[c][i + 1] - offsets[c][i]));
      } else if (kinds[c] == 2) {
        /* format(Decimal, "f"): sign, integer digits, '.', exactly
         * ``scale`` fraction digits; then msgpack's ext framing */
        int64_t v;
        std::memcpy(&v, bufs[c] + 8 * i, 8);
        uint64_t mag = v < 0 ? 0 - (uint64_t)v : (uint64_t)v;
        uint64_t p10 = (uint64_t)pow10[scales[c]];
        char text[48];
        int len = snprintf(text, sizeof text, scales[c] ? "%s%llu.%0*llu"
                           : "%s%llu", v < 0 ? "-" : "",
                           (unsigned long long)(mag / p10), scales[c],
                           (unsigned long long)(mag % p10));
        switch (len) {
          case 1: payload.push_back((char)0xD4); break;
          case 2: payload.push_back((char)0xD5); break;
          case 4: payload.push_back((char)0xD6); break;
          case 8: payload.push_back((char)0xD7); break;
          case 16: payload.push_back((char)0xD8); break;
          default: payload.push_back((char)0xC7);
                   payload.push_back((char)len);
        }
        payload.push_back((char)1);                   /* _EXT_DECIMAL */
        payload.append(text, (size_t)len);
      } else {
        int64_t v;
        std::memcpy(&v, bufs[c] + 8 * i, 8);
        mp_put_int(&payload, v);
      }
    }
    rec.clear();
    rec.push_back('P');
    put_varu64(&rec, (uint64_t)start_ts);
    if (payload.size() <= 255) {
      rec.push_back('v');
      put_varu64(&rec, (uint64_t)payload.size());
      rec += payload;
    } else {
      /* long value: payload rides the default CF at start_ts */
      std::string kd = enc;
      put_be64(&kd, TSMAX - (uint64_t)start_ts);
      mp_put_bin(&dkeys, reinterpret_cast<const uint8_t*>(kd.data()),
                 (uint32_t)kd.size());
      mp_put_bin(&dvals, reinterpret_cast<const uint8_t*>(payload.data()),
                 (uint32_t)payload.size());
      n_d++;
    }
    std::string kw = enc;
    put_be64(&kw, TSMAX - (uint64_t)commit_ts);
    mp_put_bin(&wkeys, reinterpret_cast<const uint8_t*>(kw.data()),
               (uint32_t)kw.size());
    mp_put_bin(&wvals, reinterpret_cast<const uint8_t*>(rec.data()),
               (uint32_t)rec.size());
    n_w++;
  }
  Py_END_ALLOW_THREADS

  /* payload: fixarray of [cf(fixstr), keys(array32), vals(array32)] */
  if (!g_crc32_ready) crc32_init();     /* init under the GIL */
  std::string body;
  std::string out;
  Py_BEGIN_ALLOW_THREADS
  body.reserve(wkeys.size() + wvals.size() + dkeys.size() + dvals.size()
               + 64);
  int groups = 1 + (n_d ? 1 : 0);
  body.push_back((char)(0x90 | groups));
  if (n_d) {        /* "default" sorts before "write" (v1 sorted by cf) */
    body.push_back((char)0x93);
    body.push_back((char)(0xA0 | 7));
    body.append("default");
    body.push_back((char)0xDD); put_be32(&body, (uint32_t)n_d);
    body += dkeys;
    body.push_back((char)0xDD); put_be32(&body, (uint32_t)n_d);
    body += dvals;
  }
  body.push_back((char)0x93);
  body.push_back((char)(0xA0 | 5));
  body.append("write");
  body.push_back((char)0xDD); put_be32(&body, (uint32_t)n_w);
  body += wkeys;
  body.push_back((char)0xDD); put_be32(&body, (uint32_t)n_w);
  body += wvals;

  out.reserve(body.size() + 16);
  out.append("TKVSST2\n");
  out += body;
  put_be32(&out, crc32_buf(reinterpret_cast<const uint8_t*>(body.data()),
                           body.size()));
  Py_END_ALLOW_THREADS
  return PyBytes_FromStringAndSize(out.data(), (Py_ssize_t)out.size());
}

/* ---- hash-agg finalize: the fetched Pallas accumulator -> result planes
 *
 * What device/aggregate.py's numpy chain does in ~24 array calls
 * (_sum_parts, pallas_hash.unpack_to_int64, kernels.twolevel_unpack,
 * kernels.states_from_matmul, ops/agg.py finalize_hash), in one pass
 * over the slots.  numpy drops the GIL around every inner loop of more
 * than a few hundred elements and a serving store has ~10 runnable
 * threads queued for it, so the chain's cost was the hand-offs, not
 * the arithmetic (PERF.md section 6, PR 26 and PR 28).  This function
 * never writes Py_BEGIN_ALLOW_THREADS: it holds the GIL from entry to
 * return.
 *
 * Kept in lockstep with that chain, which stays as the fallback and
 * as the oracle of tests/test_finalize_native.py:
 *  - a part is a (2, HI, p8*LO) int32 pair (lo, hi); parts add;
 *    value = lo + (hi << 16), exact in int64
 *  - slot s lives at row s / LO, lane p*LO + s % LO of plane p; slots
 *    past HI*LO read zero (the tight grid drops the NULL/scrap rows)
 *  - int SUM = sum_k plane[bp_k] << 8k + ok * bias_offset(nb), two's
 *    complement wrap-around as numpy's int64
 *  - a group is a slot whose plane-0 (row mask) count is > 0, emitted
 *    in ascending slot order; slot ``capacity`` is the NULL key, last
 *  - COUNT always valid; SUM valid where ok > 0, else 0; AVG
 *    double(sum) / double(count) where count > 0, else 0.0
 *  - no key planes (None, None): an aggregation without GROUP BY.  Its
 *    grid is ONE slot, and slot 0 is a row whether or not a row reached
 *    it: COUNT 0, SUM and AVG NULL over nothing, by the same arithmetic
 *    (ops/agg.py finalize_simple); ``capacity``, ``base`` and
 *    ``slot_keys`` are not read, the outputs hold one entry
 */

/* the one struct format code of ``b`` if it is in ``codes``, in native
 * byte order and of ``itemsize`` bytes, else 0 */
char format_code(const Py_buffer* b, Py_ssize_t itemsize, const char* codes) {
  const char* f = b->format ? b->format : "B";
  if (*f == '@' || *f == '=' || *f == '<') f++;
  if (b->itemsize != itemsize || !*f || f[1] || !strchr(codes, *f)) return 0;
  return *f;
}

/* every buffer the call holds, released on any way out */
struct Views {
  std::deque<Py_buffer> held;
  ~Views() {
    for (auto& b : held) PyBuffer_Release(&b);
  }
  /* C-contiguous buffer of ``itemsize``-byte items whose struct format
   * code is one of ``codes``; nullptr with an exception set otherwise */
  Py_buffer* get(PyObject* o, bool writable, Py_ssize_t itemsize,
                 const char* codes, const char* what) {
    held.emplace_back();
    int flags = PyBUF_C_CONTIGUOUS | PyBUF_FORMAT |
                (writable ? PyBUF_WRITABLE : 0);
    if (PyObject_GetBuffer(o, &held.back(), flags) < 0) {
      held.pop_back();
      return nullptr;
    }
    Py_buffer* b = &held.back();
    if (!format_code(b, itemsize, codes)) {
      PyErr_Format(PyExc_TypeError,
                   "hash_finalize_packed: %s has format %s, itemsize %zd",
                   what, b->format ? b->format : "(none)", b->itemsize);
      return nullptr;
    }
    return b;
  }
  /* the same of a read-only 1-D plane, for a caller that DECLINES what
   * it does not take: the view and its code in ``*code``; nullptr where
   * the buffer is something else (another format, 2-D, strided) with no
   * exception set, or where ``o`` gives no buffer at all, with one */
  const Py_buffer* plane(PyObject* o, Py_ssize_t itemsize, const char* codes,
                         char* code) {
    held.emplace_back();
    if (PyObject_GetBuffer(o, &held.back(), PyBUF_STRIDES | PyBUF_FORMAT) < 0) {
      held.pop_back();
      return nullptr;
    }
    const Py_buffer* b = &held.back();
    *code = format_code(b, itemsize, codes);
    if (!*code || b->ndim != 1 ||
        (b->shape[0] > 1 && b->strides[0] != itemsize))
      return nullptr;
    return b;
  }
};

/* a reference the call owns */
struct Drop {
  PyObject* o;
  ~Drop() { Py_XDECREF(o); }
};

enum FinKind : int64_t { FIN_COUNT_STAR = 0, FIN_COUNT = 1, FIN_SUM = 2,
                         FIN_AVG = 3 };

struct FinSpec {
  int64_t kind, ok_plane, nb;
  const int64_t* byte_planes;
  uint64_t bias;
  void* values;       /* int64, or double for AVG */
  uint8_t* validity;
};

PyObject* hash_finalize_packed(PyObject*, PyObject* args) {
  PyObject *parts_o, *slot_keys_o, *desc_o, *keys_o, *key_valid_o, *outs_o;
  Py_ssize_t LO, p8, capacity;
  long long base;
  if (!PyArg_ParseTuple(args, "OnnnLOOOOO", &parts_o, &LO, &p8, &capacity,
                        &base, &slot_keys_o, &desc_o, &keys_o, &key_valid_o,
                        &outs_o))
    return nullptr;
  if (LO <= 0 || p8 <= 0 || capacity < 0) {
    PyErr_SetString(PyExc_ValueError,
                    "hash_finalize_packed: LO, p8 > 0 and capacity >= 0");
    return nullptr;
  }
  const bool keyed = keys_o != Py_None;
  if (!keyed && key_valid_o != Py_None) {
    PyErr_SetString(PyExc_TypeError,
                    "hash_finalize_packed: key validity without a key plane");
    return nullptr;
  }
  /* + the NULL slot; one row where there is no key */
  const Py_ssize_t n_out = keyed ? capacity + 1 : 1;
  const Py_ssize_t W = p8 * LO;
  Views views;

  /* the parts: (2, HI, W) int32 each, one HI for all */
  PyObject* parts = PySequence_Fast(parts_o, "parts not a sequence");
  if (!parts) return nullptr;
  Drop drop_parts{parts};
  const Py_ssize_t n_parts = PySequence_Fast_GET_SIZE(parts);
  if (n_parts < 1) {
    PyErr_SetString(PyExc_ValueError, "hash_finalize_packed: no parts");
    return nullptr;
  }
  std::vector<const int32_t*> part_lo(n_parts);
  Py_ssize_t HI = -1;
  for (Py_ssize_t i = 0; i < n_parts; i++) {
    Py_buffer* b = views.get(PySequence_Fast_GET_ITEM(parts, i), false, 4,
                             "il", "a part");
    if (!b) return nullptr;
    if (b->len % (2 * W * 4) != 0 || (HI >= 0 && b->len != 2 * HI * W * 4)) {
      PyErr_SetString(PyExc_ValueError,
                      "hash_finalize_packed: a part is not (2, HI, p8*LO)");
      return nullptr;
    }
    HI = b->len / (2 * W * 4);
    part_lo[i] = static_cast<const int32_t*>(b->buf);
  }
  const Py_ssize_t plane_hi = HI * W;       /* lo pair -> hi pair */
  const Py_ssize_t have = HI * LO;          /* slots the grid holds */

  /* sparse recode: per-slot key values, int64 */
  const int64_t* slot_keys = nullptr;
  Py_ssize_t n_keys = 0;
  if (keyed && slot_keys_o != Py_None) {
    Py_buffer* b = views.get(slot_keys_o, false, 8, "lq", "slot_keys");
    if (!b) return nullptr;
    slot_keys = static_cast<const int64_t*>(b->buf);
    n_keys = b->len / 8;
  }

  /* outputs: n_out entries each */
  int64_t* keys = nullptr;
  uint8_t* key_valid = nullptr;
  if (keyed) {
    Py_buffer* kb = views.get(keys_o, true, 8, "lq", "the key plane");
    if (!kb) return nullptr;
    Py_buffer* kvb = views.get(key_valid_o, true, 1, "?Bb",
                               "the key validity");
    if (!kvb) return nullptr;
    if (kb->len < n_out * 8 || kvb->len < n_out) {
      PyErr_SetString(
          PyExc_ValueError,
          "hash_finalize_packed: key planes shorter than capacity+1");
      return nullptr;
    }
    keys = static_cast<int64_t*>(kb->buf);
    key_valid = static_cast<uint8_t*>(kvb->buf);
  }

  /* layouts: per spec (kind, ok_plane, nb, nb byte-plane indices) */
  Py_buffer* db = views.get(desc_o, false, 8, "lq", "the layout description");
  if (!db) return nullptr;
  const int64_t* desc = static_cast<const int64_t*>(db->buf);
  const Py_ssize_t n_desc = db->len / 8;
  PyObject* outs = PySequence_Fast(outs_o, "outs not a sequence");
  if (!outs) return nullptr;
  Drop drop_outs{outs};
  const Py_ssize_t n_specs = PySequence_Fast_GET_SIZE(outs);
  std::vector<FinSpec> specs(n_specs);
  Py_ssize_t at = 0;
  for (Py_ssize_t i = 0; i < n_specs; i++) {
    FinSpec& sp = specs[i];
    if (at + 3 > n_desc) {
      PyErr_SetString(PyExc_ValueError,
                      "hash_finalize_packed: fewer layouts than outputs");
      return nullptr;
    }
    sp.kind = desc[at];
    sp.ok_plane = desc[at + 1];
    sp.nb = desc[at + 2];
    sp.byte_planes = desc + at + 3;
    bool summed = sp.kind == FIN_SUM || sp.kind == FIN_AVG;
    if (sp.kind < FIN_COUNT_STAR || sp.kind > FIN_AVG || sp.ok_plane < 0 ||
        sp.ok_plane >= p8 || (summed ? sp.nb < 1 || sp.nb > 8 : sp.nb != 0) ||
        at + 3 + sp.nb > n_desc) {
      PyErr_SetString(PyExc_ValueError,
                      "hash_finalize_packed: bad layout description");
      return nullptr;
    }
    for (int64_t k = 0; k < sp.nb; k++)
      if (sp.byte_planes[k] < 0 || sp.byte_planes[k] >= p8) {
        PyErr_SetString(PyExc_ValueError,
                        "hash_finalize_packed: byte plane outside p8");
        return nullptr;
      }
    at += 3 + sp.nb;
    /* kernels.bias_offset: 128 * sum_k 2^(8k) - 2^(8nb-1) */
    sp.bias = 0;
    if (summed) {
      for (int64_t k = 0; k < sp.nb; k++) sp.bias += 128ULL << (8 * k);
      sp.bias -= 1ULL << (8 * sp.nb - 1);
    }
    PyObject* pair = PySequence_Fast_GET_ITEM(outs, i);
    if (!PyTuple_Check(pair) || PyTuple_GET_SIZE(pair) != 2) {
      PyErr_SetString(PyExc_TypeError,
                      "hash_finalize_packed: an output is (values, validity)");
      return nullptr;
    }
    Py_buffer* vb = views.get(PyTuple_GET_ITEM(pair, 0), true, 8,
                              sp.kind == FIN_AVG ? "d" : "lq",
                              "a value plane");
    if (!vb) return nullptr;
    Py_buffer* ob = views.get(PyTuple_GET_ITEM(pair, 1), true, 1, "?Bb",
                              "a validity plane");
    if (!ob) return nullptr;
    if (vb->len < n_out * 8 || ob->len < n_out) {
      PyErr_SetString(PyExc_ValueError,
                      "hash_finalize_packed: output shorter than its rows");
      return nullptr;
    }
    sp.values = vb->buf;
    sp.validity = static_cast<uint8_t*>(ob->buf);
  }
  if (at != n_desc) {
    PyErr_SetString(PyExc_ValueError,
                    "hash_finalize_packed: more layouts than outputs");
    return nullptr;
  }

  /* the one pass.  Arithmetic is unsigned where numpy's int64 wraps. */
  Py_ssize_t k = 0;
  const Py_ssize_t top = keyed ? capacity : 0;     /* the last slot read */
  const Py_ssize_t last = top < have ? top : have - 1;
  if (!keyed && have < 1) {
    PyErr_SetString(PyExc_ValueError,
                    "hash_finalize_packed: a grid without its one slot");
    return nullptr;
  }
  for (Py_ssize_t s = 0; s <= last; s++) {
    const Py_ssize_t cell = (s / LO) * W + s % LO;
    auto plane = [&](int64_t p) -> uint64_t {
      const Py_ssize_t j = cell + p * LO;
      uint64_t v = 0;
      for (Py_ssize_t i = 0; i < n_parts; i++)
        v += (uint64_t)((int64_t)part_lo[i][j] +
                        (int64_t)part_lo[i][plane_hi + j] * 65536);
      return v;
    };
    const int64_t mask_count = (int64_t)plane(0);
    if (!keyed) {
      /* no GROUP BY: the one row, present or not */
    } else if (mask_count <= 0) {
      continue;
    } else if (s == capacity) {
      keys[k] = 0;
      key_valid[k] = 0;
    } else {
      if (slot_keys) {
        if (s >= n_keys) {
          PyErr_Format(PyExc_IndexError,
                       "hash_finalize_packed: slot %zd is present but "
                       "slot_keys has %zd entries", s, n_keys);
          return nullptr;
        }
        keys[k] = slot_keys[s];
      } else {
        keys[k] = (int64_t)((uint64_t)s + (uint64_t)base);
      }
      key_valid[k] = 1;
    }
    for (FinSpec& sp : specs) {
      if (sp.kind == FIN_COUNT_STAR) {
        static_cast<int64_t*>(sp.values)[k] = mask_count;
        sp.validity[k] = 1;
        continue;
      }
      const uint64_t ok = plane(sp.ok_plane);
      if (sp.kind == FIN_COUNT) {
        static_cast<int64_t*>(sp.values)[k] = (int64_t)ok;
        sp.validity[k] = 1;
        continue;
      }
      uint64_t total = ok * sp.bias;
      for (int64_t b = 0; b < sp.nb; b++)
        total += plane(sp.byte_planes[b]) << (8 * b);
      const bool valid = (int64_t)ok > 0;
      sp.validity[k] = valid;
      if (sp.kind == FIN_SUM)
        static_cast<int64_t*>(sp.values)[k] = valid ? (int64_t)total : 0;
      else
        static_cast<double*>(sp.values)[k] =
            valid ? (double)(int64_t)total / (double)(int64_t)ok : 0.0;
    }
    k++;
  }
  return PyLong_FromSsize_t(k);
}

/* ---- a fast-path reply's rows: result planes -> msgpack bytes
 *
 * What server/fastpath.py's Python chain does with a ``tolist`` a
 * column, a ``zip`` to one tuple a row and ``msgpack.Packer.pack``: the
 * array of rows, each row the array of its cells, byte for byte what
 * ``msgpack.Packer(use_bin_type=True)`` writes for it.  No Python value
 * is made for a cell.  Kept in lockstep with that chain
 * (``encode_response_python``), which stays as the fallback and as the
 * oracle of tests/test_encode_native.py:
 *  - array header by length, for the row list and for a row: fixarray
 *    below 16, array16 up to 65,535, array32 beyond
 *  - an int64 or uint64 cell in the shortest form (mp_write_int /
 *    mp_write_uint), a float64 cell as 0xCB + its 8 bytes big-endian
 *  - a cell whose validity is false as 0xC0, whatever its value slot
 *    holds
 * It takes 1-D C-contiguous planes in native byte order: int64, uint64
 * or float64 values beside a bool validity, one length for all.
 * Anything else that is a buffer (an object plane, another dtype, a
 * strided view, lengths that differ, no column at all) it DECLINES by
 * returning None, and the chain serves the reply.
 */

/* Cells (rows x columns) above which the loop runs with the GIL
 * released.  Held, the loop keeps the ~20 other threads of a serving
 * store off the GIL for its length; released, this thread queues
 * behind them to take it back: 1.45-2.04 ms in the mean on a loaded
 * store (PERF.md section 5, gil.wait_ms, PR 36; PR 26 found the same
 * of numpy's inner loops).  So letting go pays only where the loop is
 * longer than that wait.  The loop runs at 7.1-8.0 ns a cell of random
 * int64 on the chip machine's host (PERF.md section 6, PR 37: 1,024 x
 * 3 fresh planes in 23 us, 65,536 x 3 in 1.39-1.50 ms): 200,000 cells
 * are ~1.5 ms.  A GROUP BY reply of 1,024 groups x 3 holds; a
 * selection's 209,380 rows x 3 (4.5-8.8 ms) lets go. */
constexpr uint64_t kEncodeReleaseCells = 200000;

enum EncKind : int { ENC_I64, ENC_U64, ENC_F64 };

struct EncCol {
  EncKind kind;
  const char* values;
  const uint8_t* validity;
};

inline char* mp_write_array_header(char* p, uint64_t n) {
  if (n < 16) { *p++ = (char)(0x90 | n); return p; }
  if (n <= 0xFFFF) return mp_write_be(p, 0xDC, n, 2);
  return mp_write_be(p, 0xDD, n, 4);
}

PyObject* encode_rows_msgpack(PyObject*, PyObject* arg) {
  Drop seq{PySequence_Fast(arg, "columns not a sequence")};
  if (!seq.o) return nullptr;
  const Py_ssize_t n_cols = PySequence_Fast_GET_SIZE(seq.o);
  if (n_cols < 1) Py_RETURN_NONE;
  Views views;
  std::vector<EncCol> cols(n_cols);
  Py_ssize_t n_rows = -1;
  for (Py_ssize_t c = 0; c < n_cols; c++) {
    PyObject* pair = PySequence_Fast_GET_ITEM(seq.o, c);
    if (!PyTuple_Check(pair) || PyTuple_GET_SIZE(pair) != 2) {
      PyErr_SetString(PyExc_TypeError,
                      "encode_rows_msgpack: a column is (values, validity)");
      return nullptr;
    }
    char code, bool_code;
    const Py_buffer* vals =
        views.plane(PyTuple_GET_ITEM(pair, 0), 8, "lqLQd", &code);
    const Py_buffer* valid =
        vals ? views.plane(PyTuple_GET_ITEM(pair, 1), 1, "?", &bool_code)
             : nullptr;
    if (!valid || vals->shape[0] != valid->shape[0] ||
        (n_rows >= 0 && vals->shape[0] != n_rows)) {
      if (PyErr_Occurred()) return nullptr;
      Py_RETURN_NONE;
    }
    n_rows = vals->shape[0];
    cols[c].kind = code == 'd' ? ENC_F64
                   : (code == 'L' || code == 'Q') ? ENC_U64 : ENC_I64;
    cols[c].values = static_cast<const char*>(vals->buf);
    cols[c].validity = static_cast<const uint8_t*>(valid->buf);
  }

  /* the most the rows can take: a 5-byte header, and a row its header
   * and 9 bytes a cell */
  char row_header[5];
  const Py_ssize_t row_header_len =
      mp_write_array_header(row_header, (uint64_t)n_cols) - row_header;
  const uint64_t row_most = (uint64_t)row_header_len + 9 * (uint64_t)n_cols;
  if ((uint64_t)n_rows > 0xFFFFFFFFULL || (uint64_t)n_cols > 0xFFFFFFFFULL ||
      (n_rows > 0 &&
       row_most > ((uint64_t)PY_SSIZE_T_MAX - 5) / (uint64_t)n_rows)) {
    PyErr_SetString(PyExc_OverflowError,
                    "encode_rows_msgpack: more rows or columns than a "
                    "msgpack array holds");
    return nullptr;
  }
  PyObject* out = PyBytes_FromStringAndSize(
      nullptr, (Py_ssize_t)(5 + row_most * (uint64_t)n_rows));
  if (!out) return nullptr;
  char* const start = PyBytes_AS_STRING(out);
  char* p = mp_write_array_header(start, (uint64_t)n_rows);

  /* the planes are held by their views and ``out`` is this call's
   * alone: the loop touches no Python object */
  PyThreadState* released =
      (uint64_t)n_rows * (uint64_t)n_cols > kEncodeReleaseCells
          ? PyEval_SaveThread() : nullptr;
  for (Py_ssize_t i = 0; i < n_rows; i++) {
    memcpy(p, row_header, row_header_len);
    p += row_header_len;
    for (const EncCol& col : cols) {
      if (!col.validity[i]) {
        *p++ = (char)0xC0;
        continue;
      }
      const char* cell = col.values + 8 * i;
      if (col.kind == ENC_I64) {
        int64_t v;
        memcpy(&v, cell, 8);
        p = mp_write_int(p, v);
      } else {
        /* a float64 goes as its bits (NaN payloads and -0.0 too) */
        uint64_t u;
        memcpy(&u, cell, 8);
        p = col.kind == ENC_U64 ? mp_write_uint(p, u)
                                : mp_write_be(p, 0xCB, u, 8);
      }
    }
  }
  if (released) PyEval_RestoreThread(released);
  if (_PyBytes_Resize(&out, p - start) < 0) return nullptr;
  return out;
}

/* gil_probe(sleep_ns) -> (woke_ns, held_ns), both CLOCK_MONOTONIC (the
 * clock of time.perf_counter_ns): sleep without the GIL, stamp the
 * wake-up, take the GIL back, stamp again.  held - woke is what a
 * thread returning from any blocking call waits before it runs Python
 * again (utils/trace.py watch_gil). */
PyObject* gil_probe(PyObject*, PyObject* args) {
  long long sleep_ns;
  if (!PyArg_ParseTuple(args, "L", &sleep_ns)) return nullptr;
  if (sleep_ns < 0) sleep_ns = 0;
  struct timespec req = {(time_t)(sleep_ns / 1000000000LL),
                         (long)(sleep_ns % 1000000000LL)};
  struct timespec woke, held;
  Py_BEGIN_ALLOW_THREADS
  nanosleep(&req, nullptr);
  clock_gettime(CLOCK_MONOTONIC, &woke);
  Py_END_ALLOW_THREADS
  clock_gettime(CLOCK_MONOTONIC, &held);
  return Py_BuildValue(
      "LL", (long long)woke.tv_sec * 1000000000LL + woke.tv_nsec,
      (long long)held.tv_sec * 1000000000LL + held.tv_nsec);
}

PyMethodDef methods[] = {
    {"mvcc_build_columnar", mvcc_build, METH_VARARGS,
     "One-pass MVCC resolve + row decode into columnar buffers.\n"
     "(keys, values, read_ts, prefix_skip, col_ids, col_kinds) -> dict"},
    {"mvcc_parse_planes", mvcc_parse_planes, METH_VARARGS,
     "Flat-plane CF_WRITE parse for device-side MVCC resolution (GIL\n"
     "released in the core loop): (keys, values, prefix_skip, col_ids,\n"
     "col_kinds) -> dict of fixed-width planes + need_default"},
    {"checksum_pairs", checksum_pairs, METH_VARARGS,
     "XOR-folded crc64-xz over (key||value) pairs -> (checksum, bytes)"},
    {"build_mvcc_sst", build_mvcc_sst, METH_VARARGS,
     "Bulk pre-timestamped MVCC SST (v2 container) from int64/float64\n"
     "column buffers: (table_id, handles_bytes, col_ids, col_kinds,\n"
     "col_bufs, col_valid, commit_ts, start_ts) -> bytes"},
    {"hash_finalize_packed", hash_finalize_packed, METH_VARARGS,
     "Fetched Pallas hash-agg accumulator -> result planes, in one call\n"
     "that holds the GIL throughout: (parts, LO, p8, capacity, base,\n"
     "slot_keys | None, layout_desc, keys_out | None, key_valid_out |\n"
     "None, [(values_out, validity_out), ...]) -> row count; no key\n"
     "planes: a grid of one slot, always one row"},
    {"encode_rows_msgpack", encode_rows_msgpack, METH_O,
     "A reply's rows as msgpack bytes, in one call that makes no Python\n"
     "value for a cell: ([(values, validity), ...]) -> bytes, the array\n"
     "of rows as msgpack.Packer(use_bin_type=True) writes it; None where\n"
     "a plane is not 1-D contiguous int64 / uint64 / float64 beside a\n"
     "bool validity of one length (the caller's Python chain serves)"},
    {"gil_probe", gil_probe, METH_VARARGS,
     "(sleep_ns) -> (woke_ns, held_ns) on CLOCK_MONOTONIC: sleep with\n"
     "the GIL released, stamp the wake-up, retake the GIL, stamp again"},
    {nullptr, nullptr, 0, nullptr}};

PyModuleDef moddef = {PyModuleDef_HEAD_INIT, "_fastbuild",
                      "native MVCC columnar builder", -1, methods,
                      nullptr, nullptr, nullptr, nullptr};

}  // namespace

PyMODINIT_FUNC PyInit__fastbuild(void) { return PyModule_Create(&moddef); }
